"""eval_fetch_ms (ms; evaluation service): mean time per evaluate batch to
bring every group's output to the host: the wait for the device plus the
device-to-host copy (program span ``eval_fetch``, one a batch)."""
from bench import program_spans


def read(run):
    return program_spans.mean_ms(run, ["eval_fetch"], "eval_fetch")
