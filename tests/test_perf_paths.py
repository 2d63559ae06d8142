"""Optimization-path equivalences (§Perf): every optimized variant must be
exact vs its naive counterpart before its measurements count."""
import jax
import jax.numpy as jnp
import pytest
from types import SimpleNamespace

from repro.configs import get_smoke_config
from repro.models import attention as attn
from repro.models import moe as M
from repro.models import transformer as T


def test_xla_mapped_attention_matches_xla():
    cfg = get_smoke_config("yi-6b").replace(d_model=64)
    p = attn.gqa_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    for s in (512, 768, 1024):
        x = jax.random.normal(jax.random.PRNGKey(1), (2, s, 64)) * 0.3
        o_x, _ = attn.gqa_apply(p, cfg.replace(attn_impl="xla"), x)
        o_m, _ = attn.gqa_apply(p, cfg.replace(attn_impl="xla_mapped"), x)
        assert float(jnp.max(jnp.abs(o_x - o_m))) < 2e-5, s


def test_xla_mapped_gradients_match():
    cfg = get_smoke_config("yi-6b").replace(d_model=64)
    p = attn.gqa_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 512, 64)) * 0.3
    g1 = jax.grad(lambda xx: attn.gqa_apply(
        p, cfg.replace(attn_impl="xla_mapped"), xx)[0].sum())(x)
    g2 = jax.grad(lambda xx: attn.gqa_apply(
        p, cfg.replace(attn_impl="xla"), xx)[0].sum())(x)
    assert float(jnp.max(jnp.abs(g1 - g2))) < 1e-4


def test_xla_mapped_pair_count_is_triangular():
    """The static λ→(i,j) tables enumerate exactly the triangular pairs."""
    import numpy as np

    from repro.core.maps import np_map_tri2d

    for nb in (4, 7, 16, 31):
        lam = np.arange(nb * (nb + 1) // 2)
        ij = np_map_tri2d(lam)
        i_np = ((np.sqrt(8 * lam + 1).astype(np.int64) - 1) // 2)
        i_np += ((i_np + 2) * (i_np + 1) // 2 <= lam)
        j_np = lam - i_np * (i_np + 1) // 2
        np.testing.assert_array_equal(np.stack([i_np, j_np], -1), ij)


def _moe_cfg(**kw):
    base = dict(d_model=32, n_experts=8, moe_top_k=2, expert_d_ff=64,
                n_shared_experts=1, capacity_factor=16.0,
                moe_renormalize=True, moe_groups=1, moe_impl="global")
    base.update(kw)
    return SimpleNamespace(**base)


def test_grouped_moe_matches_global():
    cfg = _moe_cfg()
    p = M.moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32)) * 0.5
    ref = M.moe_apply(p, cfg, x)
    for g in (2, 4, 8):
        out = M.moe_apply(p, _moe_cfg(moe_groups=g), x)
        assert float(jnp.max(jnp.abs(out - ref))) < 1e-5, g


def test_grouped_moe_gradients_match():
    cfg = _moe_cfg()
    p = M.moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32)) * 0.5
    g1 = jax.grad(lambda xx: M.moe_apply(p, cfg, xx).sum())(x)
    g2 = jax.grad(lambda xx: M.moe_apply(
        p, _moe_cfg(moe_groups=4), xx).sum())(x)
    assert float(jnp.max(jnp.abs(g1 - g2))) < 1e-4


def test_local_sort_dispatch_invariants():
    ids = jnp.asarray([3, 0, 3, 1, 3, 0, 2, 3])
    slot, keep = M._local_sort_dispatch(ids, n_buckets=4, cap=2)
    # at most `cap` kept per bucket; slots unique among kept
    kept_slots = [int(s) for s, k in zip(slot, keep) if bool(k)]
    assert len(set(kept_slots)) == len(kept_slots)
    for bucket in range(4):
        in_bucket = [s for s in kept_slots if bucket * 2 <= s < bucket * 2 + 2]
        assert len(in_bucket) <= 2
    # bucket 3 has 4 entries, cap 2 -> exactly 2 dropped
    assert int(keep.sum()) == 2 + 2 + 1 + 1


def test_mla_absorption_exact():
    cfg = get_smoke_config("deepseek-v2-236b").replace(capacity_factor=16.0)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                              cfg.vocab_size)
    _, cache = T.prefill(params, cfg, toks[:, :15])
    nt = toks[:, 15:16]
    dec_abs, _ = T.decode_step(params, cfg, nt, cache)
    dec_no, _ = T.decode_step(params, cfg.replace(mla_absorb="never"), nt,
                              cache)
    assert float(jnp.max(jnp.abs(dec_abs - dec_no))) < 2e-4


def test_moe_a2a_falls_back_without_mesh():
    """a2a config outside a mesh context must use the global path."""
    cfg = _moe_cfg(moe_impl="a2a")
    p = M.moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 32)) * 0.5
    ref = M.moe_apply(p, _moe_cfg(), x)
    out = M.moe_apply(p, cfg, x)
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-6


@pytest.mark.slow
def test_moe_a2a_subprocess():
    """a2a EP vs global MoE on 8 fake devices (fwd + grad)."""
    import os
    import subprocess
    import sys
    import textwrap

    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from types import SimpleNamespace
        from repro.models import moe as M
        from repro.distribution import sharding as shd

        mesh = shd.make_mesh((2, 4), ("data", "model"))
        cfg = SimpleNamespace(d_model=32, n_experts=8, moe_top_k=2,
                              expert_d_ff=64, n_shared_experts=1,
                              capacity_factor=8.0, moe_renormalize=True,
                              moe_groups=1, moe_impl="global")
        p = M.moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32)) * 0.5
        ref = M.moe_apply(p, cfg, x)
        g0 = jax.grad(lambda x_: M.moe_apply(p, cfg, x_).sum())(x)
        cfg2 = SimpleNamespace(**{**vars(cfg), "moe_impl": "a2a"})
        with shd.use_sharding(mesh):
            out = jax.jit(lambda p_, x_: M.moe_apply(p_, cfg2, x_))(p, x)
            g = jax.jit(jax.grad(lambda x_: M.moe_apply(p, cfg2, x_).sum()))(x)
        assert float(jnp.max(jnp.abs(out - ref))) < 1e-4
        assert float(jnp.max(jnp.abs(g - g0))) < 1e-3
        # the serving form's counters, summed over every device
        with shd.use_sharding(mesh):
            out_s, counts = jax.jit(lambda p_, x_: M.moe_serve(p_, cfg2, x_))(
                p, x)
        assert float(jnp.max(jnp.abs(out_s - ref))) < 1e-4
        c = dict(zip(M.COUNTERS, counts.tolist()))
        assert c["routed"] == c["to_held"] == 4 * 16 * 2, c
        assert c["held_computed"] == 8 and 0 < c["held_hit"] <= 8, c
        assert c["dropped"] == 0, c
        print("OK a2a")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "OK a2a" in res.stdout


def test_serving_engine_greedy():
    from repro.serving.engine import generate

    cfg = get_smoke_config("granite-8b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                 cfg.vocab_size)
    res = generate(params, cfg, prompts, max_new_tokens=8)
    assert res.tokens.shape == (2, 16)
    # greedy generation must match teacher-forced argmax step by step
    logits = T.forward(params, cfg, res.tokens[:, :-1])
    preds = jnp.argmax(logits[:, :, : cfg.vocab_size], axis=-1)
    assert bool((preds[:, 7:] == res.tokens[:, 8:]).all())
