"""The program's own spans in a run's window, for the per-layer readers.

The served evaluate path records its stages with ``repro.obs.trace``
(``eval_queue``, ``eval_plan``, ``eval_dispatch``, ``eval_fetch``,
``eval_assemble``, ``wire_encode``, ``wire_send``, ``wire_drain``) into a
process-wide ring of ``(name, t0, t1, cpu_s, attrs)`` on ``time.monotonic``,
which outlives the server.  ``between`` reads the spans that ended in the
window; a program that keeps no such ring gives None, and so does every
reader built on it.
"""
from __future__ import annotations


def between(run, names) -> dict | None:
    """{name: [(name, t0, t1, cpu_s, attrs)]} of the spans of ``names``
    that ended in the run's window, or None where the program has no ring
    or no span of any of ``names`` ended there."""
    try:
        from repro.obs import trace
    except ImportError:
        return None
    read = getattr(trace, "spans_between", None)
    if read is None:
        return None
    got: dict = {n: [] for n in names}
    for s in read(*run.window):
        if s[0] in got:
            got[s[0]].append(s)
    return got if any(got.values()) else None


def wall(spans) -> float:
    """Summed wall seconds."""
    return sum(s[2] - s[1] for s in spans)


def mean_ms(run, total, per) -> float | None:
    """Summed wall time of the spans of ``total`` over the number of spans
    of ``per`` (one a request or a batch), in ms; None when the window
    holds none of ``per``."""
    got = between(run, {*total, per})
    if got is None or not got[per]:
        return None
    return sum(wall(got[n]) for n in total) / len(got[per]) * 1e3
