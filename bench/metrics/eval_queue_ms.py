"""eval_queue_ms (ms; frontend + wire): mean wait of an evaluate request in
the asyncio frontend's worker pool, from submit on the event loop to the
start on a worker (program span ``eval_queue``, one a request that
offloads)."""
from bench import program_spans


def read(run):
    return program_spans.mean_ms(run, ["eval_queue"], "eval_queue")
