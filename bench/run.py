"""Run one benchmark cell once, on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``; its
configuration, traffic mix, generator and metric readers are found by name
under ``bench/`` (see ``common.py``).  One run:

1. names the device and exits non-zero, printing no result, unless JAX finds
   a TPU with at least the chips the cell asks for;
2. points JAX's persistent compilation cache at ``<checkout>/.jax_cache``
   (or ``$JAX_COMPILATION_CACHE_DIR``), so only a checkout's first run of a
   cell compiles;
3. builds the server in this process with ``repro.launch.serve`` from the
   configuration's arguments, over a new, empty artifact store, and starts
   the load generator (``client.py``, processes that never touch the
   chip);
4. set-up: the generator's ``warm`` runs every shape the traffic can use;
5. load starts, and after ``ramp_s`` the window opens and stays open for
   ``--seconds``; with ``--trace 1`` the profiler records it;
6. once the window has closed: the device's peak memory is read, the
   generator's ``check`` compares what the window served with the plain
   reference, and the metric readers turn the run into numbers.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (with ``--trace 1`` also
``busy_s``/``window_s``, and a ``breakdown``), ``load``, and last ``checks``:
each number compared beside its limit, which are also the last lines on
standard error.

``--control 1`` also reads the control's numbers (the reference in the
precision below the configuration's, or with one of its guarantees broken)
from the same window; the benchmark's own runs leave it off.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import queue  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parent / "src")]

from bench import common, spans  # noqa: E402
from bench import trace as tr  # noqa: E402


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def require_devices(chips: int) -> dict:
    """The devices JAX finds; no TPU, or fewer chips than the cell asks
    for, ends the run without a result."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) < chips:
        raise SystemExit(f"{len(devs)} chips visible, the cell needs {chips}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def sleep_until(t: float) -> None:
    while True:
        left = t - time.monotonic()
        if left <= 0:
            return
        time.sleep(min(left, 0.05))


class Client:
    """One load-generating process and its line protocol (``client.py``)."""

    def __init__(self, spec: dict):
        env = dict(os.environ, JAX_PLATFORMS="cpu", OMP_NUM_THREADS="1",
                   OPENBLAS_NUM_THREADS="1", MKL_NUM_THREADS="1")
        self.proc = subprocess.Popen(
            [sys.executable, str(HERE / "client.py")], stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, env=env, text=True, cwd=str(HERE.parent))
        self.lines: queue.Queue = queue.Queue()
        self.reader = threading.Thread(target=self._read, daemon=True)
        self.reader.start()
        self.out = spec["out"]
        self._send(spec)

    def _read(self) -> None:
        for line in self.proc.stdout:
            self.lines.put(line.strip())
        self.lines.put(None)

    def _send(self, msg: dict) -> None:
        self.proc.stdin.write(json.dumps(msg) + "\n")
        self.proc.stdin.flush()

    def _expect(self, word: str, timeout: float) -> None:
        try:
            line = self.lines.get(timeout=timeout)
        except queue.Empty:
            raise RuntimeError(f"load generator: no {word!r} within "
                               f"{timeout:.0f}s") from None
        if line != word:
            raise RuntimeError(f"load generator said {line!r}, not {word!r}")

    def ready(self, timeout: float) -> None:
        self._expect("ready", timeout)

    def go(self, msg: dict) -> None:
        self._send(msg)

    def result(self, timeout: float) -> dict:
        import pickle

        self._expect("done", timeout)
        with open(self.out, "rb") as f:  # written by this run's own client
            return pickle.load(f)

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait(timeout=30)
        self.reader.join(timeout=5)


def merge(parts: list) -> dict:
    """The client processes' results as one: lists joined, numbers added."""
    out: dict = {}
    for part in parts:
        for k, v in part.items():
            if k not in out:
                out[k] = list(v) if isinstance(v, list) else v
            else:
                out[k] = out[k] + v
    return out


class Clients:
    """The load generator: the mix's ``clients`` threads split among its
    ``processes`` (default 1), thread i in process i mod processes, so that
    no one interpreter's lock paces the load."""

    def __init__(self, spec: dict):
        n = spec["mix"]["clients"]
        p = min(spec["mix"].get("processes", 1), n)
        self.procs: list = []
        for i in range(p):
            self.procs.append(Client({**spec, "threads": list(range(i, n, p)),
                                      "out": f"{spec['out']}.{i}"}))

    def ready(self, timeout: float) -> None:
        for c in self.procs:
            c.ready(timeout)

    def go(self, msg: dict) -> None:
        for c in self.procs:
            c.go(msg)

    def result(self, timeout: float) -> dict:
        parts = [c.result(timeout) for c in self.procs]
        out = merge(parts)
        out["cpu_s_max"] = max(p["cpu_s"] for p in parts)
        return out

    def stop(self) -> None:
        for c in self.procs:
            c.stop()


class Ctx:
    """What a generator's server-side hooks see."""

    def __init__(self, config, mix, seed, rec):
        self.config = config
        self.mix = mix
        self.seed = seed
        self.rec = rec
        self.stack = None
        self.url = None
        self.window = (0.0, 0.0)

    def free(self) -> None:
        """Stop the server and let its state (the model's weights among it)
        leave the device."""
        if self.stack is None:
            return
        self.stack.close()
        self.stack = None
        gc.collect()


def cpu_seconds() -> float:
    """User and system CPU seconds of this process, every thread."""
    ru = resource.getrusage(resource.RUSAGE_SELF)
    return ru.ru_utime + ru.ru_stime


def memory_peak() -> int | None:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_cell(cell: dict, config: dict, mix: dict, seed: int, seconds: float,
             trace: bool, control: bool, device: dict) -> dict:
    """One run of one cell; returns the result object."""
    import jax

    from repro.launch import serve

    kind = common.traffic_kind(mix["kind"])
    work = Path(tempfile.mkdtemp(prefix="bench-run-"))
    # a new, empty artifact store: every derive in the window is fresh
    os.environ["REPRO_ARTIFACT_CACHE"] = str(work / "store")
    rec = spans.Recorder()
    rec.capture_tokens()
    if trace:
        rec.install_spans()
    ctx = Ctx(config, mix, seed, rec)
    clients = Clients({"root": str(HERE.parent), "src": str(common.SRC),
                       "mix": mix, "config": config, "seed": seed,
                       "out": str(work / "clients.pkl")})
    summary = None
    try:
        args = serve.build_parser().parse_args(
            [*config["serve_args"], "--port", "0"])
        ctx.stack = serve.build_map_server(args)
        ctx.url = ctx.stack.url
        shared = kind.warm(ctx)
        clients.ready(timeout=300)
        t_load = time.monotonic() + 0.05
        t_w0 = t_load + mix["ramp_s"]
        t_w1 = t_w0 + seconds
        ctx.window = (t_w0, t_w1)
        clients.go({"url": ctx.url, "shared": shared, "t_load": t_load,
                    "t_w0": t_w0, "t_w1": t_w1})
        if trace:
            jax.profiler.start_trace(str(work / "trace"))
        sleep_until(t_w0)
        setup_s = t_w0 - T_PROCESS
        before = kind.counters(ctx)
        cpu0 = cpu_seconds()
        if trace:
            with jax.profiler.TraceAnnotation("bench:window"):
                sleep_until(t_w1)
        else:
            sleep_until(t_w1)
        after = kind.counters(ctx)
        server_cpu_s = cpu_seconds() - cpu0
        if trace:
            jax.profiler.stop_trace()
        result = clients.result(timeout=seconds + 600)
        peak = memory_peak()
        checks = kind.check(ctx, result, control)
        if trace:
            summary = tr.summarize(tr.find_xplane(str(work / "trace")))
            top = sorted(summary["per_module"].items(), key=lambda kv: -kv[1][1])
            log(f"trace programs (runs, seconds): {top[:10]}")
    finally:
        ctx.free()
        clients.stop()
        rec.uninstall()
        shutil.rmtree(work, ignore_errors=True)

    w0, w1 = ctx.window
    done = [r for r in result["records"] if w0 <= r["t1"] < w1]
    failed = sum(1 for r in done if not r["ok"])
    checks["failed_requests"] = {"value": failed, "limit": 0}
    run = common.Run(cell=cell, config=config, mix=mix, seed=seed,
                     seconds=seconds, window=ctx.window, setup_s=setup_s,
                     requests=result["records"], rec=rec,
                     counters={"before": before, "after": after},
                     trace=summary, peaks=common.peaks(device["kind"]))
    metrics = {}
    for m in common.metrics_for(cell["name"], trace):
        value = common.metric_reader(m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        else:
            log(f"metric {m['name']}: nothing to read in this run")
    correct = bool(done) and all(
        c["value"] is not None and c["value"] <= c["limit"]
        for c in checks.values())
    out = {"correct": correct, "attempted": len(done), "failed": failed,
           "metrics": metrics,
           "device": {**device, "memory_peak_bytes": peak}}
    if summary is not None:
        out["device"].update(busy_s=summary["busy_s"],
                             window_s=summary["window_s"])
        out["breakdown"] = tr.breakdown(summary)
    # requests answered in each second of the window: a stall of the host
    # shows as a second that answered few
    per_s = [0] * max(1, int(seconds + 0.999))
    for r in done:
        per_s[min(int(r["t1"] - w0), len(per_s) - 1)] += 1
    out["load"] = {"client_threads": mix["clients"],
                   "client_processes": mix.get("processes", 1),
                   "client_cpu_cores": result["cpu_s"] / seconds,
                   "client_cpu_cores_busiest": result["cpu_s_max"] / seconds,
                   "server_cpu_cores": server_cpu_s / seconds,
                   "client_errors": result["errors"],
                   "server_rss_peak_bytes": resource.getrusage(
                       resource.RUSAGE_SELF).ru_maxrss * 1024,
                   "answered_per_s": per_s,
                   "counters": {"before": before, "after": after}}
    out["checks"] = checks
    return out


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--control", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def main(argv=None) -> int:
    opts = parse_args(argv)
    cell = common.cell(opts.workload)
    config = common.config(cell["config"])
    mix = common.mix(cell["traffic"])
    from repro.core.compile_cache import enable_persistent_cache

    enable_persistent_cache()
    import jax

    # every program, however quick to compile, so that a checkout's later
    # runs compile nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    device = require_devices(cell["chips"])
    log(f"device: {device}")
    out = run_cell(cell, config, mix, opts.seed, opts.seconds,
                   bool(opts.trace), bool(opts.control), device)
    for name, c in out["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
