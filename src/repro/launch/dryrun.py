"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

This is the proof that the distribution config is coherent without real
hardware: 512 placeholder host devices back the production meshes
(16x16 single-pod, 2x16x16 multi-pod); every cell must lower AND compile,
and the compiled artifact yields the memory/cost/collective numbers the
roofline analysis (launch/roofline.py) consumes.

Usage:
    python -m repro.launch.dryrun --arch yi-6b --shape train_4k
    python -m repro.launch.dryrun --all [--multi-pod both] --out results/dryrun

The model path sets ``XLA_FLAGS`` for the 512 placeholder devices in
``main``, before JAX first touches a backend; ``--domain-map`` runs on
whatever device JAX finds.
"""
import argparse
import json
import os
import time
import traceback

import jax

from repro.configs import ARCH_IDS, SHAPES, applicable, get_config
from repro.distribution import sharding as shd
from repro.launch import analytic, hlo_analysis
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import act_rules_for, input_specs


def apply_profile(cfg, shape, profile: str, overrides: dict | None = None):
    """Named optimization profiles (§Perf iterations).

    baseline  — the paper-faithful naive deployment (BB-masked XLA attention,
                global MoE dispatch, no MLA absorption, no microbatching).
    optimized — the beyond-paper configuration: mapped triangular attention
                scan, grouped MoE dispatch, MLA weight absorption (decode),
                8-way microbatch accumulation, sequence-parallel attention
                fallback for head counts that don't divide the tensor axis.
    """
    from repro.train.train_step import TrainConfig

    tcfg = None
    rules = act_rules_for(shape)
    if profile == "optimized":
        over = {"mla_absorb": "auto"}
        # the XLA-mapped grid pays when heads can't shard the tensor axis
        # (λ-axis SP recovers the 16x) or when attention is a small slice of
        # a MoE layer; heads-divisible dense archs keep the head-sharded
        # chunked path (measured: mapped+gather duplication regresses them —
        # on real TPU the Pallas mapped kernel provides the 2x instead).
        odd_heads = cfg.n_heads and cfg.n_heads % 16 != 0
        if shape.kind in ("train", "prefill") and (
                odd_heads or cfg.family == "moe"):
            over["attn_impl"] = "xla_mapped"
        if cfg.family == "moe":
            over["moe_impl"] = "a2a"   # grouped dispatch: moe_groups=16
        cfg = cfg.replace(**over)
        if shape.kind == "train":
            # microbatch only when saved layer-boundary activations would
            # overflow HBM (batch/16 per device, bf16, ~2 passes):
            eff_layers = (cfg.decoder_layers if cfg.family == "audio"
                          else cfg.n_layers)  # encoder seq is fixed/short
            act_gb = (eff_layers * (shape.global_batch / 16)
                      * shape.seq_len * cfg.d_model * 2 * 2) / 1e9
            if act_gb > 8.0:
                tcfg = TrainConfig(microbatches=8)
        if odd_heads:
            rules = {**rules, "attn_seq": "model"}
    else:
        cfg = cfg.replace(mla_absorb="never")
    for k, v in (overrides or {}).items():
        if k.startswith("rule:"):
            rules = {**rules, k[5:]: v}
        elif k == "microbatches":
            from repro.train.train_step import TrainConfig as TC

            tcfg = TC(microbatches=v)
        else:
            cfg = cfg.replace(**{k: v})
    return cfg, tcfg, rules


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             verbose: bool = True, profile: str = "baseline",
             overrides: dict | None = None) -> dict:
    """Lower + compile one cell; returns the roofline-input record."""
    shape = SHAPES[shape_name]
    cfg = get_config(arch).replace(max_seq=shape.seq_len, attn_impl="xla")
    ok, reason = applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
                "status": "skipped", "reason": reason}
    cfg, tcfg, rules = apply_profile(cfg, shape, profile, overrides)
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    with shd.use_sharding(mesh, act_rules=rules):
        fn, args, donate = input_specs(cfg, shape, mesh, tcfg=tcfg,
                                       rules=rules)
        jitted = jax.jit(fn, donate_argnums=donate)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    record = {
        "arch": arch, "shape": shape_name,
        "multi_pod": multi_pod, "mesh": dict(mesh.shape),
        "status": "ok", "kind": shape.kind, "profile": profile,
        "seq_len": shape.seq_len, "global_batch": shape.global_batch,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
    }
    try:
        ca = compiled.cost_analysis()
        ca = ca[0] if isinstance(ca, (list, tuple)) else ca
        record["xla_cost_flops_raw"] = float(ca.get("flops", 0.0))
        record["xla_bytes_raw"] = float(ca.get("bytes accessed", 0.0))
    except Exception as e:  # pragma: no cover
        record["cost_analysis_error"] = repr(e)
    try:
        ma = compiled.memory_analysis()
        record["memory_analysis"] = {
            attr: int(getattr(ma, attr))
            for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                         "temp_size_in_bytes", "alias_size_in_bytes")
            if hasattr(ma, attr)
        }
    except Exception as e:  # pragma: no cover
        record["memory_analysis_error"] = repr(e)
    try:
        # trip-count-aware per-device numbers from the post-SPMD HLO
        h = hlo_analysis.analyze(compiled.as_text())
        record["hlo"] = {
            "flops_per_device": h["flops"],
            "hbm_bytes_per_device": h["hbm_bytes"],
            "per_op_flops": h["per_op_flops"],
            "collectives": {
                k: v for k, v in h["collectives"].items()},
        }
    except Exception as e:  # pragma: no cover
        record["hlo_error"] = repr(e)
    try:
        record["analytic"] = analytic.cell_analytics(cfg, shape)
    except Exception as e:  # pragma: no cover
        record["analytic_error"] = repr(e)
    if verbose:
        mp = "2x16x16" if multi_pod else "16x16"
        hf = record.get("hlo", {}).get("flops_per_device", 0)
        cb = record.get("hlo", {}).get("collectives", {}).get("total_bytes", 0)
        print(f"[dryrun] {arch} x {shape_name} x {mp}: OK "
              f"(lower {record['lower_s']}s, compile {record['compile_s']}s, "
              f"hlo_flops/dev {hf:.3e}, coll/dev {cb:.3e} B)",
              flush=True)
    return record


def run_domain_map_cell(artifact, n_points: int = 65_536,
                        block_n: int = 1024, verbose: bool = True) -> dict:
    """Deploy a validated ``MappingArtifact`` through the mapped-grid Pallas
    kernel and verify the compiled coordinates against the artifact's own
    validated scalar map — the Phase-4 integration proof for one artifact."""
    import numpy as np

    from repro.kernels.domain_map.ops import block_counts, map_coordinates
    from repro.serving.evaluate import auto_interpret

    interpret = auto_interpret()
    t0 = time.time()
    coords = map_coordinates(artifact, n_points, block_n=block_n,
                             interpret=interpret)
    t_run = time.time() - t0
    sample = np.linspace(0, n_points - 1, 256, dtype=np.int64)
    scalar = artifact.scalar_fn()
    ok = all(tuple(coords[i]) == tuple(scalar(int(i))) for i in sample)
    record = {
        "kind": "domain_map", "status": "ok" if ok else "mismatch",
        "domain": artifact.domain, "model": artifact.model,
        "stage": artifact.stage, "logic": artifact.logic,
        "report_digest": artifact.report_digest,
        "n_points": n_points, "block_n": block_n, "interpret": interpret,
        "kernel_s": round(t_run, 3),
        "blocks": block_counts(artifact, n_points, block_n),
        "analytic": analytic.artifact_deployment_analytics(artifact),
    }
    if verbose:
        a = record["analytic"]
        print(f"[dryrun] domain-map {artifact.domain} x {artifact.model} "
              f"s{artifact.stage}: {record['status']} "
              f"(kernel {t_run:.2f}s, projected speedup {a['speedup']:.0f}x, "
              f"energy {a['energy_reduction']:.0f}x)", flush=True)
    return record


def _run_domain_map(domain_name: str, model: str, out_dir: str) -> None:
    from repro.core.backends import MockLLMBackend
    from repro.core.domains import get_domain
    from repro.core.pipeline import derive_mapping

    res = derive_mapping(get_domain(domain_name), MockLLMBackend(model),
                         stage=100, n_validate=50_000, sample_every=10)
    art = res.artifact
    if art is None or not art.deployable:
        raise SystemExit(
            f"derivation not deployable: {domain_name} x {model} "
            f"(ordered {res.report.ordered_pct:.2f}%, error={res.error!r})")
    rec = run_domain_map_cell(art)
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"domain_map__{domain_name}__{model}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    if rec["status"] != "ok":
        raise SystemExit(f"domain-map dry-run MISMATCH: {path}")


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", choices=ARCH_IDS)
    p.add_argument("--shape", choices=tuple(SHAPES))
    p.add_argument("--all", action="store_true")
    p.add_argument("--multi-pod", choices=("off", "on", "both"), default="both")
    p.add_argument("--out", default="results/dryrun")
    p.add_argument("--skip-existing", action="store_true")
    p.add_argument("--profile", choices=("baseline", "optimized"),
                   default="baseline")
    p.add_argument("--domain-map", metavar="DOMAIN",
                   help="derive + deploy one domain's MappingArtifact "
                        "through the Pallas mapped kernel instead of an "
                        "(arch x shape) cell")
    p.add_argument("--map-model", default="OSS:120b",
                   help="backend model for --domain-map")
    args = p.parse_args()

    if args.domain_map:
        _run_domain_map(args.domain_map, args.map_model, args.out)
        return

    # 512 placeholder host devices back the production meshes
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    cells: list[tuple[str, str]] = []
    if args.all:
        cells = [(a, s) for a in ARCH_IDS for s in SHAPES]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]
    pods = {"off": (False,), "on": (True,), "both": (False, True)}[args.multi_pod]

    os.makedirs(args.out, exist_ok=True)
    failures = []
    for arch, shape in cells:
        for mp in pods:
            tag = f"{arch}__{shape}__{'mp' if mp else 'sp'}"
            if args.profile != "baseline":
                tag += f"__{args.profile}"
            path = os.path.join(args.out, tag + ".json")
            if args.skip_existing and os.path.exists(path):
                print(f"[dryrun] {tag}: cached, skipping")
                continue
            try:
                rec = run_cell(arch, shape, mp, profile=args.profile)
            except Exception as e:
                traceback.print_exc()
                rec = {"arch": arch, "shape": shape, "multi_pod": mp,
                       "status": "failed", "error": repr(e)}
                failures.append(tag)
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
    if failures:
        raise SystemExit(f"dry-run FAILURES: {failures}")
    print("[dryrun] all requested cells passed")


if __name__ == "__main__":
    main()
