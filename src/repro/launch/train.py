"""Training launcher — end-to-end driver usable on CPU (reduced configs) and
on real TPU topologies (full configs; same code path as the dry-run).

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-3b --smoke \
        --steps 200 --batch 8 --seq 256 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.core.compile_cache import enable_persistent_cache
from repro.distribution import sharding as shd
from repro.launch.mesh import make_local_mesh, make_production_mesh
from repro.models import transformer as T
from repro.models.common import count_params
from repro.train import checkpoint as ckpt
from repro.train import optimizer as opt
from repro.train.data import DataConfig, SyntheticLM
from repro.train.fault_tolerance import ResilientLoop, StepWatchdog
from repro.train.train_step import TrainConfig, make_train_step


def build(cfg, mesh, tcfg: TrainConfig, seed: int = 0):
    """Init sharded params + opt state and the jitted train step."""
    with shd.use_sharding(mesh):
        param_shapes = jax.eval_shape(
            lambda k: T.init_params(k, cfg), jax.random.PRNGKey(seed))
        p_sh = shd.param_sharding(T.param_specs(cfg), param_shapes, mesh)
        params = jax.jit(
            lambda k: T.init_params(k, cfg), out_shardings=p_sh
        )(jax.random.PRNGKey(seed))
        o_logical = opt.state_specs(T.param_specs(cfg))
        o_shapes = jax.eval_shape(opt.init_state, params)
        o_sh = shd.param_sharding(o_logical, o_shapes, mesh)
        opt_state = jax.jit(opt.init_state, out_shardings=o_sh)(params)
        step = jax.jit(make_train_step(cfg, tcfg), donate_argnums=(0, 1))
    return params, opt_state, step


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True)
    p.add_argument("--smoke", action="store_true",
                   help="reduced same-family config (CPU-runnable)")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=256)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--microbatches", type=int, default=1)
    p.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    p.add_argument("--ckpt-every", type=int, default=50)
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--resume", action="store_true")
    p.add_argument("--production-mesh", action="store_true")
    p.add_argument("--d-model", type=int, default=0,
                   help="override width (e.g. ~100M-param runs)")
    p.add_argument("--n-layers", type=int, default=0)
    p.add_argument("--d-ff", type=int, default=0)
    args = p.parse_args()
    enable_persistent_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    over = {"max_seq": args.seq}
    if args.d_model:
        over["d_model"] = args.d_model
    if args.n_layers:
        over["n_layers"] = args.n_layers
    if args.d_ff:
        over["d_ff"] = args.d_ff
    cfg = cfg.replace(**over)

    mesh = (make_production_mesh() if args.production_mesh
            else make_local_mesh())
    tcfg = TrainConfig(
        optimizer=opt.OptimizerConfig(lr=args.lr, total_steps=args.steps,
                                      warmup_steps=max(args.steps // 20, 5)),
        microbatches=args.microbatches,
    )
    params, opt_state, step = build(cfg, mesh, tcfg)
    n = count_params(params)
    print(f"arch={cfg.arch_id} params={n:,} mesh={dict(mesh.shape)}")

    data = SyntheticLM(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq, global_batch=args.batch))

    def batch_fn(step_idx: int):
        b = data.batch_at(step_idx)
        if cfg.family in ("vlm", "audio"):
            n_extra = cfg.vision_seq if cfg.family == "vlm" else cfg.encoder_seq
            rng = np.random.default_rng(step_idx)
            b["extra"] = rng.standard_normal(
                (args.batch, n_extra, cfg.d_model), dtype=np.float32) * 0.1
        return jax.tree.map(jnp.asarray, b)

    start = 0
    if args.resume:
        last = ckpt.latest_step(args.ckpt_dir)
        if last is not None:
            restored, _ = ckpt.restore(
                args.ckpt_dir, last,
                {"params": params, "opt_state": opt_state})
            params, opt_state = restored["params"], restored["opt_state"]
            start = last
            print(f"resumed from step {start}")

    def run_step(params, opt_state, batch):
        with shd.use_sharding(mesh):
            return step(params, opt_state, batch)

    loop = ResilientLoop(
        step_fn=run_step, batch_fn=batch_fn, ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every, watchdog=StepWatchdog())
    t0 = time.time()
    params, opt_state, info = loop.run(
        params, opt_state, start, args.steps, log_every=args.log_every)
    dt = time.time() - t0
    print(f"done: {info['final_step'] - start} steps in {dt:.1f}s "
          f"({dt / max(info['final_step'] - start, 1):.2f} s/step), "
          f"restores={info['restores']}, "
          f"median_step={loop.watchdog.median:.3f}s")
    final = {k: float(v) for k, v in (info["metrics"] or {}).items()}
    print("final metrics:", final)


if __name__ == "__main__":
    main()
