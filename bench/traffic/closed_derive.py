"""Closed-loop ``POST /v1/derive`` traffic on the engine backend.

One client process runs ``clients`` threads; thread i starts at
``t_load + i * start_spacing_s``, so that no two first requests fall into
one admission of the batcher, and then derives fresh cells back to back.
Domains are dealt from a deck, a permutation of ``domains`` drawn from
``--seed``: the k-th derive of thread i takes entry (i + clients * k) mod
len(domains), so that every seed asks for each domain as often, in another
order.  Stages are taken without replacement from a permutation of
``stages`` drawn from the seed.  The artifact store is new and empty in
every run, so every derive runs the engine: prefill, then decode through
the cohort's KV cache, then synthesis and validation.

Set-up warms prefill and decode for every cohort size up to the decode
slots by calling the server's own ``EngineStepper``.

After the window the check takes a sample of the derives completed in it,
drawn from the seed, and compares every token the engine chose with the
plain float32 reference run over the same prompt and tokens: the widest gap
by which a chosen token's reference logit lies below the reference's best.
It also evaluates a few of the published artifacts through their keys and
compares the coordinates with the plain map reference.
"""
from __future__ import annotations

import threading
import time

import numpy as np


# -- server side -------------------------------------------------------------

def warm(ctx) -> dict:
    import jax

    cfg = ctx.config
    service = ctx.stack.service
    service.request_key(ctx.mix["domains"][0], cfg["model_label"])
    stepper = service.backends()[cfg["model_label"]].batcher.stepper
    for rows in range(1, cfg["decode_slots"] + 1):
        state = stepper.prefill(["set-up"] * rows)
        stepper.step(state)
        jax.block_until_ready(state.tok)
    return {}


def counters(ctx) -> dict:
    backend = ctx.stack.service.backends().get(ctx.config["model_label"])
    return {"engine": backend.stats.as_dict() if backend else {}}


# -- client side -------------------------------------------------------------

def _thread(spec, thread, deck, stages, t_load, t_w1, out):
    from repro.serving import RemoteMappingService

    mix, label = spec["mix"], spec["config"]["model_label"]
    n = mix["clients"]
    client = RemoteMappingService(spec["url"], timeout=600, retries=0)
    records = []
    t_start = t_load + thread * mix["start_spacing_s"]
    while time.monotonic() < t_start:
        time.sleep(0.001)
    try:
        for k, stage in enumerate(stages):
            if time.monotonic() >= t_w1:
                break
            domain = mix["domains"][deck[(thread + n * k) % len(deck)]]
            t0 = time.monotonic()
            rec = dict(domain=domain, stage=int(stage), kind="derive")
            try:
                res = client.derive(domain, label, int(stage))
                rec.update(ok=bool(res.perfect and not res.cache_hit),
                           tokens=int(res.response.tokens_out),
                           key=res.cache_key)
            except Exception as e:  # noqa: BLE001 — a failed request counts
                rec.update(ok=False, tokens=0, key=None)
                out["errors"].append(repr(e)[:300])
            rec.update(t0=t0, t1=time.monotonic())
            records.append(rec)
        else:
            out["errors"].append(f"thread {thread} ran out of stages")
    finally:
        client.close()
    out["records"][thread] = records


def client_run(spec: dict, shared: dict, t_load: float, t_w0: float,
               t_w1: float) -> dict:
    mix = spec["mix"]
    n = mix["clients"]
    lo, hi = mix["stages"]
    rng = np.random.default_rng(spec["seed"])
    perm = rng.permutation(np.arange(lo, hi + 1))
    deck = rng.permutation(len(mix["domains"]))
    out = {"records": [None] * n, "errors": []}
    threads = [threading.Thread(target=_thread, args=(
        spec, i, deck, perm[i::n], t_load, t_w1, out))
        for i in spec.get("threads", range(n))]
    for t in threads:
        t.start()
    while time.monotonic() < t_w0:
        time.sleep(0.01)
    cpu0 = time.process_time()
    while time.monotonic() < t_w1:
        time.sleep(0.01)
    cpu_s = time.process_time() - cpu0
    for t in threads:
        t.join()
    return {"records": [r for rs in out["records"] if rs for r in rs],
            "cpu_s": cpu_s, "errors": out["errors"][:5]}


# -- after the window, server side -------------------------------------------

def _sample(ctx, records: list) -> list:
    """Derives completed in the window, drawn from the seed, the longest
    first."""
    w0, w1 = ctx.window
    done = [r for r in records if r["ok"] and w0 <= r["t1"] < w1]
    done.sort(key=lambda r: (-r["tokens"], r["stage"]))
    rng = np.random.default_rng([ctx.seed, 2])
    k = min(ctx.mix["sample_derives"], len(done))
    if k == 0:
        return []
    rest = rng.choice(np.arange(1, len(done)), size=k - 1, replace=False) \
        if k > 1 else []
    return [done[0]] + [done[i] for i in sorted(rest)]


def check(ctx, result: dict, control: bool) -> dict:
    from repro.serving import RemoteMappingService

    from bench.common import config
    from bench.reference import llama, maps

    cfg, mix = ctx.config, ctx.mix
    limits = cfg["limits"]
    picked = _sample(ctx, result["records"])
    specs = config(mix["domain_specs"])["domains"]

    # published artifacts, evaluated through their keys
    mismatched, compared = 0, 0
    client = RemoteMappingService(ctx.url, timeout=600, retries=0)
    try:
        for r in picked[:mix["artifact_checks"]]:
            got = client.evaluate(key=r["key"], n_points=mix["artifact_points"])
            ref = maps.coords(specs[r["domain"]], 0, mix["artifact_points"])
            mismatched += maps.mismatches(got.get("coords"), ref)
            compared += ref.size
    finally:
        client.close()

    served = ctx.rec.served_tokens()
    rows = [served[(r["domain"], r["stage"])] for r in picked]
    ctx.free()  # the program's weights leave the chip before the reference
    checks = {}
    if rows:
        prompts = np.stack([p for p, _ in rows])
        tokens = np.stack([t for _, t in rows])
        gap, gap_ctl = llama.token_gaps(cfg, prompts, tokens, control)
        checks["logit_gap"] = {"value": float(gap.max()),
                               "limit": limits["logit_gap"],
                               "tokens": int(gap.size),
                               "derives": len(rows)}
        if control:
            checks["control_logit_gap"] = {"value": float(gap_ctl.max()),
                                           "limit": limits["logit_gap"]}
    else:
        checks["logit_gap"] = {"value": None, "limit": limits["logit_gap"],
                               "tokens": 0, "derives": 0}
    checks["coord_mismatches"] = {"value": mismatched,
                                  "limit": limits["coord_mismatches"],
                                  "compared_coords": compared}
    return checks
