"""Finding the benchmark's items by name.

Every item lives in a file of its own, and the harness holds no table of
them: a cell is an entry of ``BENCHMARK.json``'s ``workloads``; its
configuration is ``configs/<config>.json``; its traffic mix is
``traffic/<traffic>.json``, whose ``kind`` names the generator
``traffic/<kind>.py``; a metric is the reader ``metrics/<name>.py``.
Adding any of them means adding files and entries, never editing these.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
SRC = ROOT / "src"


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def cell(name: str) -> dict:
    for w in benchmark()["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def config(name: str) -> dict:
    return load_json(BENCH / "configs" / f"{name}.json")


def mix(name: str) -> dict:
    return load_json(BENCH / "traffic" / f"{name}.json")


def _module(path: Path, label: str):
    spec = importlib.util.spec_from_file_location(label, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def traffic_kind(kind: str):
    """The generator module ``traffic/<kind>.py``."""
    return _module(BENCH / "traffic" / f"{kind}.py", f"bench_traffic_{kind}")


def metric_reader(name: str):
    """The reader ``metrics/<name>.py`` (its ``read(run)`` gives the value
    or None when the run holds nothing for it to read)."""
    return _module(BENCH / "metrics" / f"{name}.py",
                   "bench_metric_" + name.replace(".", "_"))


def metrics_for(cell_name: str, trace: bool) -> list[dict]:
    """The metrics a run of this cell reports: the end-to-end ones with
    ``--trace 0``, the per-layer ones with ``--trace 1``.  A metric with a
    ``workloads`` list applies to those cells; one without applies to every
    cell (a per-layer one, to every cell that reports its ``moves``)."""
    bench = benchmark()
    e2e = [m for m in bench["end_to_end"]
           if cell_name in m.get("workloads", [cell_name])]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if cell_name in m.get("workloads", [cell_name])
            and m["moves"] in moved]


@dataclasses.dataclass
class Run:
    """One finished run, as the metric readers see it.  Times are
    ``time.monotonic`` seconds; ``window`` is the measured window.

    * ``requests`` — every request the load generator sent: ``kind``,
      ``t0``/``t1`` (sent, answered), ``ok`` and its size (``points`` and
      ``bytes`` for evaluates, ``tokens`` for derives);
    * ``rec``      — the harness's spans, compile events and captured tokens
      (``spans.Recorder``; the timing spans only with ``--trace 1``);
    * ``counters`` — the generator's counter snapshots ``before`` and
      ``after`` the window;
    * ``trace``    — ``trace.reduce``'s summary (``--trace 1`` only);
    * ``peaks``    — this device kind's entry of ``peaks.json``."""

    cell: dict
    config: dict
    mix: dict
    seed: int
    seconds: float
    window: tuple
    setup_s: float
    requests: list
    rec: object
    counters: dict
    trace: dict | None
    peaks: dict

    def answered(self, kind: str) -> list:
        """Requests of ``kind`` answered without error inside the window."""
        w0, w1 = self.window
        return [r for r in self.requests
                if r["kind"] == kind and r["ok"] and w0 <= r["t1"] < w1]

    def sent(self, kind: str) -> list:
        """Requests of ``kind`` sent inside the window."""
        w0, w1 = self.window
        return [r for r in self.requests
                if r["kind"] == kind and w0 <= r["t0"] < w1]

    def prorated(self, kind: str, field: str) -> float:
        """Sum of ``field`` over the answered requests of ``kind``, each
        weighted by the share of its time (sent to answered) that lies in
        the window: the work the window itself carried."""
        w0, w1 = self.window
        total = 0.0
        for r in self.requests:
            if r["kind"] != kind or not r["ok"]:
                continue
            inside = min(r["t1"], w1) - max(r["t0"], w0)
            if inside > 0:
                total += r[field] * inside / max(r["t1"] - r["t0"], 1e-9)
        return total

    def spans(self, name: str) -> list:
        """(name, t0, t1, info) of the harness spans of ``name`` that ended
        in the window."""
        return self.rec.in_window(name, *self.window)

    def compiles(self) -> int:
        """Executables JAX built inside the window."""
        w0, w1 = self.window
        return sum(1 for t in self.rec.compiles if w0 <= t < w1)

    def device_seconds(self, match) -> float | None:
        """Device time of the trace's ops whose name ``match`` accepts;
        None without a trace or when no such op ran."""
        if self.trace is None:
            return None
        s = sum(v for n, v in self.trace["per_op_s"].items() if match(n))
        return s or None


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of this kind.  A kind the table does
    not hold is an error, never a default."""
    table = load_json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise SystemExit(f"no peaks for device kind {device_kind!r} in "
                         f"bench/peaks.json")
    return table[device_kind]
