"""Closed-loop ``POST /v1/derive`` traffic, checked against the plain
reference that the configuration names.

The traffic is ``closed_derive``'s: set-up, counters and the client threads
are its own (``warm``, ``counters``, ``client_run``).  The check takes the
reference module ``bench/reference/<config's "reference">.py`` for the
logit gaps, and adds the expert layer's guarantee: no (token, expert)
assignment to a held expert was dropped in any finished cohort
(``dropped_assignments``, from the engine's ``moe_dropped`` counter; a
program that keeps no such counter reads None, which is not correct).
"""
from __future__ import annotations

import importlib

import numpy as np

from bench.common import traffic_kind

_base = traffic_kind("closed_derive")
warm = _base.warm
counters = _base.counters
client_run = _base.client_run


def _dropped(ctx):
    backend = ctx.stack.service.backends().get(ctx.config["model_label"])
    if backend is None:
        return None
    return backend.stats.as_dict().get("moe_dropped")


def check(ctx, result: dict, control: bool) -> dict:
    from repro.serving import RemoteMappingService

    from bench.common import config
    from bench.reference import maps

    cfg, mix = ctx.config, ctx.mix
    limits = cfg["limits"]
    ref = importlib.import_module(f"bench.reference.{cfg['reference']}")
    picked = _base._sample(ctx, result["records"])
    specs = config(mix["domain_specs"])["domains"]

    # published artifacts, evaluated through their keys
    mismatched, compared = 0, 0
    client = RemoteMappingService(ctx.url, timeout=600, retries=0)
    try:
        for r in picked[:mix["artifact_checks"]]:
            got = client.evaluate(key=r["key"], n_points=mix["artifact_points"])
            want = maps.coords(specs[r["domain"]], 0, mix["artifact_points"])
            mismatched += maps.mismatches(got.get("coords"), want)
            compared += want.size
    finally:
        client.close()

    dropped = _dropped(ctx)
    served = ctx.rec.served_tokens()
    rows = [served[(r["domain"], r["stage"])] for r in picked]
    ctx.free()  # the program's weights leave the chip before the reference
    checks = {}
    if rows:
        prompts = np.stack([p for p, _ in rows])
        tokens = np.stack([t for _, t in rows])
        gap, gap_ctl = ref.token_gaps(cfg, prompts, tokens, control)
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
    checks["dropped_assignments"] = {"value": dropped,
                                     "limit": limits["dropped_assignments"]}
    return checks
