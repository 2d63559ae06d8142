"""host_wait_share (%; host (interpreter lock)): the share of the wall time
of the pure-host spans of the evaluate path (``eval_plan``,
``eval_dispatch``, ``eval_assemble``, ``wire_encode``, ``wire_send``) in
which their thread was not on a CPU: 100 x (1 - CPU seconds / wall
seconds), the CPU seconds being each span's ``time.thread_time`` delta.
What is left is the wait for Python's interpreter lock or the scheduler."""
from bench import program_spans

HOST_SPANS = ("eval_plan", "eval_dispatch", "eval_assemble", "wire_encode",
              "wire_send")


def read(run):
    got = program_spans.between(run, HOST_SPANS)
    if got is None:
        return None
    spans = [s for n in HOST_SPANS for s in got[n] if s[3] is not None]
    wall = program_spans.wall(spans)
    if wall <= 0:
        return None
    return 100.0 * (1.0 - sum(s[3] for s in spans) / wall)
