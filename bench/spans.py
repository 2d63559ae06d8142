"""Spans and counters the benchmark records from its own files, by wrapping
the calls into each layer that it can reach without editing the program:

* ``EvaluationService.evaluate_batch``   -> ``evaluate_batch``
* ``EngineStepper.prefill/step/finalize`` -> ``engine_prefill`` /
  ``engine_step`` / ``engine_finalize``
* ``pipeline.stage_validation``          -> ``stage_validation``

Each wrapper times the call on the host clock and opens a
``jax.profiler.TraceAnnotation`` named ``bench:<span>``, so that a traced run
can name what the host was doing while the device sat idle.  The engine
spans time the dispatch of asynchronous device work, not the work itself.

``Recorder.capture_tokens`` keeps, for every finished cohort, the prompt and
the tokens the engine chose, which the check compares with the reference.
It is on in every run; the timing wrappers only where per-layer metrics are
read (``--trace 1``).
"""
from __future__ import annotations

import functools
import threading
import time

#: JAX's monitoring event for every executable it builds (compiled, or
#: loaded from the persistent compilation cache)
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Recorder:
    def __init__(self):
        self.spans: list[tuple[str, float, float, dict]] = []
        self.compiles: list[float] = []
        self.tokens: list[dict] = []
        self._mu = threading.Lock()
        self._undo: list = []

    # -- recording ---------------------------------------------------------
    def _span(self, name, t0, t1, info):
        with self._mu:
            self.spans.append((name, t0, t1, info))

    def _wrap(self, owner, attr, name, info_fn):
        import jax

        orig = getattr(owner, attr)

        @functools.wraps(orig)
        def wrapper(*args, **kwargs):
            t0 = time.monotonic()
            with jax.profiler.TraceAnnotation(f"bench:{name}"):
                out = orig(*args, **kwargs)
            self._span(name, t0, time.monotonic(), info_fn(args, out))
            return out

        setattr(owner, attr, wrapper)
        self._undo.append((owner, attr, orig))

    # -- installation ------------------------------------------------------
    def install_spans(self) -> None:
        import jax

        from repro.core import pipeline
        from repro.serving.async_engine import EngineStepper
        from repro.serving.evaluate import EvaluationService

        self._wrap(EvaluationService, "evaluate_batch", "evaluate_batch",
                   _evaluate_info)
        self._wrap(EngineStepper, "prefill", "engine_prefill",
                   lambda a, out: {"rows": int(out.toks.shape[0]),
                                   "prompt": int(out.toks.shape[1])})
        self._wrap(EngineStepper, "step", "engine_step",
                   lambda a, out: {"rows": int(a[1].toks.shape[0]),
                                   "position": int(a[1].toks.shape[1])
                                   + a[1].steps_done - 1})
        self._wrap(pipeline, "stage_validation", "stage_validation",
                   lambda a, out: {})

        def on_event(event, duration, **kwargs):
            if event == BACKEND_COMPILE_EVENT:
                with self._mu:
                    self.compiles.append(time.monotonic())

        jax.monitoring.register_event_duration_secs_listener(on_event)
        self._undo.append(
            (None, "listener",
             lambda: jax.monitoring.unregister_event_duration_listener(
                 on_event)))

    def capture_tokens(self) -> None:
        from repro.serving.async_engine import EngineStepper

        orig = EngineStepper.finalize
        rec = self

        @functools.wraps(orig)
        def finalize(stepper, state, metas):
            import jax

            t0 = time.monotonic()
            with jax.profiler.TraceAnnotation("bench:engine_finalize"):
                out = orig(stepper, state, metas)
            t1 = time.monotonic()
            # device arrays are kept as they are; read back after the window
            with rec._mu:
                rec.tokens.append({"prompts": state.toks,
                                   "generated": list(state.generated),
                                   "metas": [(m.get("domain"), m.get("stage"))
                                             for m in metas]})
                rec.spans.append(("engine_finalize", t0, t1,
                                  {"rows": len(metas)}))
            return out

        EngineStepper.finalize = finalize
        self._undo.append((EngineStepper, "finalize", orig))

    def uninstall(self) -> None:
        for owner, attr, orig in reversed(self._undo):
            if owner is None:
                orig()
            else:
                setattr(owner, attr, orig)
        self._undo.clear()

    # -- reading -----------------------------------------------------------
    def in_window(self, name: str, t0: float, t1: float) -> list:
        """Spans of ``name`` that ended inside [t0, t1)."""
        with self._mu:
            return [s for s in self.spans
                    if s[0] == name and t0 <= s[2] < t1]

    def served_tokens(self) -> dict:
        """(domain, stage) -> (prompt ids, served ids) as numpy arrays."""
        import numpy as np

        out = {}
        with self._mu:
            cohorts = list(self.tokens)
        for c in cohorts:
            gen = np.concatenate([np.asarray(t) for t in c["generated"]],
                                 axis=1)
            for row, meta in enumerate(c["metas"]):
                out[meta] = (np.asarray(c["prompts"][row]), gen[row])
        return out


def _evaluate_info(args, out) -> dict:
    """Per executable group of the batch: the points asked (the widest
    member: the others are its prefixes) and the coordinates per point."""
    results, _ = out
    asked: dict[int, tuple[int, int]] = {}
    for r in results:
        arr = r.get("coords")
        if arr is None:
            continue
        n, d = asked.get(r["group"], (0, arr.shape[1]))
        asked[r["group"]] = (max(n, r["n_points"]), d)
    return {"queries": len(results),
            "launches": [{"points": n, "dim": d} for n, d in asked.values()]}
