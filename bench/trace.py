"""Reduction of a profiler trace to the numbers the per-layer metrics read.

The JAX profiler writes ``<dir>/plugins/profile/<time>/*.xplane.pb``; it is
read with ``jax.profiler.ProfileData``.  Device planes are those named
``/device:TPU:<n>``; their ``XLA Ops`` line holds one event per operation
run on the chip.  Host spans are the ``bench:<span>`` annotations that
``spans.py`` opens, on the host plane and on the same clock.

* busy      — length of the union of the device's op intervals;
* per op    — summed device time of each op name (kernels by their names);
* idle gaps — the stretches between busy intervals inside the window, each
  named by the harness span that overlaps it most (``none`` where no span
  was open: the host was serving something that never reached the device,
  or waiting for a request).
"""
from __future__ import annotations

import glob
from collections import defaultdict

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench:"


def find_xplane(logdir: str) -> str:
    paths = sorted(glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return paths[-1]


def op_name(name: str) -> str:
    """An op's name without its HLO text: a TPU trace names an op by its
    whole instruction, ``%tpu_custom_call.1 = s32[8,2097152]{1,0:T(8,128)}
    custom-call(), ...``, which becomes ``tpu_custom_call.1 s32[8,2097152]``
    (the shape kept, so that launches of different sizes stay apart)."""
    if not name.startswith("%") or " = " not in name:
        return name
    head, rest = name[1:].split(" = ", 1)
    return f"{head} {rest.split('{', 1)[0].split(' ', 1)[0]}"


def events(path: str) -> tuple[dict, list]:
    """({device plane: {line: [(name, start_ns, end_ns)]}}, host spans
    [(name, start_ns, end_ns)]) from one trace file: the device's ops (by
    ``op_name``) and its program (module) runs, and the harness's spans."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict[str, list] = {}
    spans: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {OPS_LINE: [], MODULES_LINE: []}
            for line in plane.lines:
                if line.name in lines:
                    lines[line.name].extend(
                        (op_name(e.name), e.start_ns,
                         e.start_ns + e.duration_ns)
                        for e in line.events)
            devices[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name[len(SPAN_PREFIX):], e.start_ns,
                                      e.start_ns + e.duration_ns))
    return devices, spans


def union(intervals: list) -> list:
    """Merged, sorted (start, end) intervals."""
    out: list = []
    for s, e in sorted((s, e) for _, s, e in intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def gaps(busy: list, lo: float, hi: float) -> list:
    """Idle (start, end) stretches of [lo, hi] between busy intervals."""
    out = []
    t = lo
    for s, e in busy:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def _overlap(a0, a1, b0, b1) -> float:
    return max(0.0, min(a1, b1) - max(a0, b0))


def name_gap(gap: tuple, spans: list) -> str:
    """The harness span that overlaps the gap most, else ``none``."""
    best, name = 0.0, "none"
    for n, s, e in spans:
        ov = _overlap(gap[0], gap[1], s, e)
        if ov > best:
            best, name = ov, n
    return name


def _clip(events: list, lo: float, hi: float) -> list:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def reduce(devices: dict, spans: list, window_ns: tuple | None = None
           ) -> dict:
    """Busy and idle seconds, per-op and per-module device seconds, and
    named idle gaps.

    ``window_ns`` bounds the traced window; by default it runs from the
    first to the last event the trace holds.  Busy is averaged over the
    device planes."""
    every = [t for lines in devices.values() for evs in lines.values()
             for _, s, e in evs for t in (s, e)]
    every += [t for _, s, e in spans for t in (s, e)]
    if window_ns is None:
        window_ns = (min(every), max(every)) if every else (0.0, 0.0)
    lo, hi = window_ns
    per_op: dict[str, float] = defaultdict(float)
    per_module: dict[str, list] = defaultdict(lambda: [0, 0.0])
    busy_total = 0.0
    named: list = []
    for lines in devices.values():
        ops = _clip(lines[OPS_LINE], lo, hi)
        for n, s, e in ops:
            per_op[n] += (e - s) / 1e9
        for n, s, e in _clip(lines[MODULES_LINE], lo, hi):
            per_module[n][0] += 1
            per_module[n][1] += (e - s) / 1e9
        busy = union(ops)
        busy_total += sum(e - s for s, e in busy) / 1e9
        named += [(name_gap(g, spans), (g[1] - g[0]) / 1e9)
                  for g in gaps(busy, lo, hi)]
    n_dev = max(len(devices), 1)
    return {
        "devices": len(devices),
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_total / n_dev,
        "per_op_s": dict(per_op),
        "per_module": {n: tuple(v) for n, v in per_module.items()},
        "gaps": sorted(named, key=lambda g: -g[1]),
    }


def summarize(path: str) -> dict:
    """``reduce`` over the window the harness marked with its ``window``
    span (the whole trace where it is missing)."""
    devices, spans = events(path)
    window = [(s, e) for n, s, e in spans if n == "window"]
    return reduce(devices, [s for s in spans if s[0] != "window"],
                  window[0] if window else None)


def breakdown(summary: dict, top: int = 10) -> dict:
    """The ``breakdown`` of a traced run's result line."""
    ops = sorted(summary["per_op_s"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in summary["gaps"][:top]]}
