"""Smoke run of the mapping server on TPU, through its normal entry points.

    python chip_smoke.py             # one chip: phases (a)-(d)
    python chip_smoke.py --chips 4   # four chips: the multi-device sweep only

One process does everything: the server built by ``repro.launch.serve``'s
own parser (its frontend and batcher are threads of this process), the
binary-wire client, and the model checks — a chip belongs to one process.

One chip, on one server (``--backend engine --arch yi-6b``, published
widths, random weights from seed 0):

  (a) evaluate  — one ``POST /v1/evaluate`` batch: the ``map`` tier at 2^21
                  points and the ``membership`` tier over a bounding box of
                  at most 2^21 cells, for every domain with deployable
                  kernels; each answer equals the numpy ground truth integer
                  for integer and reports ``interpret: false``; the repeat
                  batch reports ``executable: hit``.
  (b) derive    — 4 cells derived concurrently through the continuous
                  batcher into a fresh, empty artifact store, then each
                  evaluated through its returned artifact key.
  (c) cache     — prefill + 8 decode steps through the KV cache; each step's
                  logits against ``T.forward`` over the same tokens.
  (d) kernel    — cache-free ``T.forward`` at S=4096 with the mapped Pallas
                  attention kernel against the XLA attention path.

Four chips: one ``POST /v1/evaluate`` sweep over the same domains at 2^21
points on the sharded path, against the numpy ground truth and the
one-device Pallas answer; each cell reports ``devices: 4`` and its output
lives on four devices.

Lines before the last are smoke timings of one cold run, not metrics.  The
last line is ``{"ok": true, "device": {...}}``.  With no TPU the script
exits non-zero and prints no result; any failed check or phase exits
non-zero.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
import traceback
from concurrent.futures import ThreadPoolExecutor

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

#: every domain with deployable map + membership kernels (menger3d has no
#: deployable derivation: the paper's Menger limit)
DOMAINS = ("tri2d", "pyramid3d", "gasket2d", "carpet2d", "sierpinski3d",
           "msimplex4", "msimplex5", "cantor2d", "vicsek2d")
DERIVE_CELLS = ("tri2d", "pyramid3d", "gasket2d", "msimplex4")
MODEL = "OSS:120b"
ARCH = "yi-6b"
SEED = 0
DECODE_STEPS = 8
FORWARD_SEQ = 4096
#: relative L2 error allowed between two bf16 programs that compute the same
#: logits in a different order (cached vs full attention; Pallas vs XLA
#: attention).  bf16 keeps 8 mantissa bits (2^-8 = 0.4% per rounding); the
#: two programs round independently through 32 layers, which stays within a
#: few such roundings, while a wrong cache row, position or kernel block
#: changes the logits by O(1) of their norm.
LOGIT_REL_TOL = 2e-2
#: a cold request may compile full-width programs before it answers
CLIENT_TIMEOUT = 600.0


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def peak_bytes() -> int | None:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


class timed:
    """Prints one smoke-timing line per phase (wall seconds, peak bytes)."""

    def __init__(self, phase: str):
        self.phase = phase
        self.extra: dict = {}

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, *_):
        if exc_type is None:
            fields = {"wall_s": time.perf_counter() - self.t0, **self.extra,
                      "peak_bytes_in_use": peak_bytes()}
            log(f"smoke timing (one cold run, not a metric) phase="
                f"{self.phase} " + " ".join(f"{k}={v}"
                                            for k, v in fields.items()))


def box_extent(name: str, max_cells: int) -> list[int]:
    """The domain's bounding box for the most points whose box still fits
    ``max_cells`` cells (binary search: the box grows with the points)."""
    import numpy as np

    from repro.core.domains import DOMAINS as ALL

    dom = ALL[name]
    lo, hi = 1, max_cells
    while lo < hi:
        mid = (lo + hi + 1) // 2
        if int(np.prod(dom.bounding_box_extent(mid))) <= max_cells:
            lo = mid
        else:
            hi = mid - 1
    return [int(e) for e in dom.bounding_box_extent(lo)]


def ground_truth_map(name: str, n_points: int):
    import numpy as np

    from repro.core.maps import np_map

    return np_map(name, np.arange(n_points, dtype=np.int64))


def check_map(res: dict, name: str, truth) -> None:
    import numpy as np

    check(res["interpret"] is False,
          f"{name} map ran in interpret mode")
    got = np.asarray(res["coords"]).astype(np.int64)
    check(got.shape == truth.shape and np.array_equal(got, truth),
          f"{name} map differs from the numpy ground truth")


def phase_evaluate(client, n_points: int) -> None:
    import numpy as np

    from repro.kernels.domain_map.ref import bb_membership_ref

    boxes = {name: box_extent(name, n_points) for name in DOMAINS}
    queries = [{"domain": name, "n_points": n_points} for name in DOMAINS]
    queries += [{"domain": name, "tier": "membership", "extent": boxes[name]}
                for name in DOMAINS]
    with timed("a.evaluate") as t:
        t0 = time.perf_counter()
        first = client.evaluate_batch(queries)
        t.extra["first_batch_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        again = client.evaluate_batch(queries)
        t.extra["repeat_batch_s"] = time.perf_counter() - t0
        t.extra["compile_s"] = client.metrics()["compile_cache"][
            "trace_seconds"]
        for name, res in zip(DOMAINS, first[:len(DOMAINS)]):
            check_map(res, name, ground_truth_map(name, n_points))
        for name, res in zip(DOMAINS, first[len(DOMAINS):]):
            check(res["interpret"] is False,
                  f"{name} membership ran in interpret mode")
            truth = bb_membership_ref(name, tuple(boxes[name])).astype(bool)
            check(np.array_equal(np.asarray(res["mask"]), truth),
                  f"{name} membership differs from the numpy ground truth")
        for a, b in zip(first, again):
            check(b["executable"] == "hit",
                  f"repeat {b['domain']} {b['tier']} was not a hit")
            field = "coords" if a["tier"] == "map" else "mask"
            check(np.array_equal(a[field], b[field]),
                  f"repeat {a['domain']} {a['tier']} changed its answer")
    log(f"a.evaluate: {len(queries)} queries exact, interpret=false, "
        f"repeat all hit; boxes={boxes}")


def engine_backend(stack):
    """The server's continuous-batching engine backend for ``MODEL``."""
    stack.service.request_key(DERIVE_CELLS[0], MODEL)  # builds the backend
    return stack.service.backends()[MODEL]


def phase_derive(stack, n_points: int) -> None:
    from repro.serving import RemoteMappingService

    backend = engine_backend(stack)
    with timed("b.warm") as t:
        params, cfg, _, _ = backend.batcher.stepper.model()
        t.extra["layers"] = cfg.n_layers
        t.extra["d_model"] = cfg.d_model

    def derive(name):
        client = RemoteMappingService(stack.url, timeout=CLIENT_TIMEOUT)
        try:
            return client.derive(name, MODEL, 100)
        finally:
            client.close()

    with timed("b.derive") as t:
        with ThreadPoolExecutor(len(DERIVE_CELLS)) as pool:
            results = list(pool.map(derive, DERIVE_CELLS))
        stats = backend.stats.as_dict()
        t.extra.update(cohorts=stats["cohorts"], prefills=stats["prefills"],
                       steps=stats["steps"])
    check(stack.service.stats.derivations == len(DERIVE_CELLS),
          f"{stack.service.stats.derivations} derivations, "
          f"want {len(DERIVE_CELLS)}")
    check(stats["completed"] == len(DERIVE_CELLS),
          f"continuous batcher completed {stats['completed']} requests")
    client = RemoteMappingService(stack.url, timeout=CLIENT_TIMEOUT)
    try:
        with timed("b.deploy"):
            for name, res in zip(DERIVE_CELLS, results):
                check(not res.cache_hit, f"{name} came from a cache")
                check(res.perfect, f"{name} derivation is not perfect")
                got = client.evaluate(key=res.cache_key, n_points=n_points)
                check(got["domain"] == name, f"{name} key resolved to "
                      f"{got['domain']}")
                check_map(got, name, ground_truth_map(name, n_points))
    finally:
        client.close()
    log(f"b.derive: {len(DERIVE_CELLS)} cells derived at {cfg.arch_id} "
        f"widths (layers={cfg.n_layers}, d_model={cfg.d_model}) and deployed "
        f"through their artifact keys, exact at {n_points} points")


def rel_err(got, want) -> float:
    import numpy as np

    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    check(np.isfinite(got).all() and np.isfinite(want).all(),
          "non-finite logits")
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def phase_cache(stepper, prompt_len: int, steps: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.models import transformer as T

    params, cfg, prefill, step = stepper.model()
    check(prompt_len + steps <= cfg.max_seq, "cache too small")
    with timed("c.cache") as t:
        toks = jax.random.randint(jax.random.PRNGKey(SEED + 1),
                                  (1, prompt_len), 0, cfg.vocab_size,
                                  jnp.int32)
        logits, cache = prefill(params, toks)
        cached = [logits]
        fed = [toks]
        tok = jnp.argmax(logits[:, -1:, :cfg.vocab_size], -1).astype(
            jnp.int32)
        for _ in range(steps):
            fed.append(tok)
            logits, cache = step(params, tok, cache)
            cached.append(logits)
            tok = jnp.argmax(logits[:, :, :cfg.vocab_size], -1).astype(
                jnp.int32)
        full = jax.jit(lambda p, x: T.forward(p, cfg, x))(
            params, jnp.concatenate(fed, axis=1))
        errs = [rel_err(cached[0], full[:, :prompt_len])]
        errs += [rel_err(c, full[:, prompt_len + i:prompt_len + i + 1])
                 for i, c in enumerate(cached[1:])]
        t.extra["max_rel_err"] = max(errs)
    log(f"c.cache: prefill + {steps} decode steps against T.forward, "
        f"rel errors {[float(f'{e:.3g}') for e in errs]} "
        f"(tol {LOGIT_REL_TOL})")
    for i, e in enumerate(errs):
        check(e <= LOGIT_REL_TOL,
              f"{'prefill' if i == 0 else f'decode step {i}'} logits off "
              f"T.forward by {e:.3g} (tol {LOGIT_REL_TOL})")


def phase_kernel(stepper, seq: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import transformer as T
    from repro.serving.evaluate import auto_interpret

    params, cfg, _, _ = stepper.model()
    toks = jax.random.randint(jax.random.PRNGKey(SEED + 2), (1, seq), 0,
                              cfg.vocab_size, jnp.int32)
    out = {}
    with timed("d.kernel") as t:
        for impl in ("xla", "pallas_mapped"):
            c = cfg.replace(max_seq=seq, attn_impl=impl,
                            pallas_interpret=auto_interpret())
            t0 = time.perf_counter()
            compiled = jax.jit(lambda p, x, c=c: T.forward(p, c, x)).lower(
                params, toks).compile()
            t.extra[f"compile_s_{impl}"] = time.perf_counter() - t0
            has_kernel = "tpu_custom_call" in compiled.as_text()
            check(has_kernel == (impl == "pallas_mapped"),
                  f"attn_impl={impl}: custom kernel in program={has_kernel}")
            out[impl] = np.asarray(compiled(params, toks))
        err = rel_err(out["pallas_mapped"], out["xla"])
        t.extra["rel_err"] = err
    log(f"d.kernel: T.forward S={seq} with the mapped Pallas attention "
        f"kernel against XLA attention, rel error {err:.3g} "
        f"(tol {LOGIT_REL_TOL})")
    check(err <= LOGIT_REL_TOL, f"mapped-kernel logits off XLA attention by "
          f"{err:.3g} (tol {LOGIT_REL_TOL})")


def phase_sharded_sweep(client, n_points: int, n_dev: int) -> None:
    import numpy as np

    with timed("sweep") as t:
        swept = list(client.evaluate_sweep(list(DOMAINS), [n_points]))
        single = client.evaluate_batch(
            [{"domain": name, "n_points": n_points} for name in DOMAINS])
        t.extra["cells"] = len(swept)
    check([c["domain"] for c in swept] == list(DOMAINS), "sweep cells")
    for name, cell, one in zip(DOMAINS, swept, single):
        check(cell["executable"] == "sharded", f"{name} was not sharded")
        check(cell["devices"] == n_dev, f"{name} devices={cell['devices']}")
        check(cell["shard_devices"] == n_dev,
              f"{name} output lives on {cell['shard_devices']} devices")
        truth = ground_truth_map(name, n_points)
        got = np.asarray(cell["coords"]).astype(np.int64)
        check(np.array_equal(got, truth),
              f"{name} sharded sweep differs from the numpy ground truth")
        check_map(one, name, truth)
        log(f"sweep {name}: n_points={n_points} devices={cell['devices']} "
            f"shard_devices={cell['shard_devices']} == numpy == one-device "
            f"Pallas")


def serve_args(*extra: str):
    from repro.launch import serve

    return serve.build_parser().parse_args(
        ["--serve-maps", "--port", "0", *extra])


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4: run only the multi-device sweep (needs 4 chips)")
    opts = p.parse_args()

    import jax

    from repro.core.compile_cache import enable_persistent_cache
    from repro.launch import serve
    from repro.serving import RemoteMappingService
    from repro.serving.evaluate import MAX_POINTS

    enable_persistent_cache()
    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"no TPU: JAX found {devs[0].platform} devices")
    check(len(devs) == opts.chips,
          f"{len(devs)} chips visible, --chips {opts.chips}")
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    log(f"device: {device}")

    with tempfile.TemporaryDirectory(prefix="chip-smoke-store-") as store:
        # a fresh, empty artifact store: every artifact deployed below was
        # derived in this run, from this checkout's code
        os.environ["REPRO_ARTIFACT_CACHE"] = store
        if opts.chips == 4:
            stack = serve.build_map_server(serve_args())
        else:
            # full-width prefill/decode compile while later derives wait for
            # a decode slot: give admission room for a cold compile
            stack = serve.build_map_server(serve_args(
                "--backend", "engine", "--arch", ARCH,
                "--admission-timeout", "600"))
        serve.print_banner(stack)
        client = RemoteMappingService(stack.url, timeout=CLIENT_TIMEOUT)
        if opts.chips == 4:
            phases = [lambda: phase_sharded_sweep(client, MAX_POINTS,
                                                  len(devs))]
        else:
            def stepper():
                return engine_backend(stack).batcher.stepper

            phases = [
                lambda: phase_evaluate(client, MAX_POINTS),
                lambda: phase_derive(stack, MAX_POINTS),
                lambda: phase_cache(stepper(), prompt_len=48,
                                    steps=DECODE_STEPS),
                lambda: phase_kernel(stepper(), FORWARD_SEQ),
            ]
        # every phase runs, so one chip run reports every failure; any
        # failure still fails the run
        failed = 0
        try:
            for phase in phases:
                try:
                    phase()
                except Exception:  # noqa: BLE001 — reported, then exit 1
                    traceback.print_exc()
                    failed += 1
        finally:
            client.close()
            stack.close()
    if failed:
        log(f"FAILED: {failed} of {len(phases)} phases")
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
