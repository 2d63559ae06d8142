"""Attention variants: GQA (RoPE, optional qk-norm), MLA (DeepSeek-V2
compressed KV), and cross-attention.  Every variant supports full-sequence
(train / prefill) and single-step decode against a cache.

The XLA softmax path is *q-chunked* (scan over query blocks with per-block
masks built from positions, never materializing (S, T) probabilities — the
same block decomposition the paper's triangular map induces).  Peak memory
per layer is one (B, H, chunk, T) block.  The causal self-attention score
space is the paper's 2D lower-triangular domain; `cfg.attn_impl` selects:
  * "xla"           — chunked einsum + mask (dry-run/compile analysis path),
  * "pallas_mapped" — mapped linear-λ-grid Pallas kernel (paper technique),
  * "pallas_bb"     — bounding-box Pallas kernel (paper baseline).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.distribution.sharding import logical_constraint as lc
from repro.models.common import (
    EMBED, HEAD_DIM, HEADS, KV_HEADS, dense_init, rms_norm, rope,
    yarn_softmax_scale,
)

NEG_INF = -1e30
_Q_CHUNK = 256


def _sdpa(q, k, v, n_kv_heads, q_pos=None, chunk: int = _Q_CHUNK,
          scale: float | None = None):
    """Grouped SDPA, fp32 softmax, q-chunked.

    q: (B, S, H, D); k, v: (B, T, Hk, D).
    q_pos: (B, S) absolute positions — causal mask "kv_index <= q_pos";
           None => no mask (cross / bidirectional attention).
    scale: softmax scale (defaults to D^-0.5 — MLA passes its own).
    """
    b, s, h, d = q.shape
    t = k.shape[1]
    dv = v.shape[-1]
    g = h // n_kv_heads
    scale = d ** -0.5 if scale is None else scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    kv_idx = jnp.arange(t)

    def block(q_blk, pos_blk):
        """q_blk: (B, C, H, D); pos_blk: (B, C) or None."""
        qg = q_blk.reshape(b, -1, n_kv_heads, g, d).astype(jnp.float32)
        logits = jnp.einsum("bskgd,btkd->bkgst", qg, kf) * scale
        if pos_blk is not None:
            mask = kv_idx[None, :] <= pos_blk[..., None]      # (B, C, T)
            logits = jnp.where(mask[:, None, None, :, :], logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bkgst,btkd->bskgd", probs, vf)
        return out.reshape(b, -1, h, dv).astype(q.dtype)

    if s <= chunk or s % chunk != 0:
        return block(q, q_pos)

    nc = s // chunk
    qc = q.reshape(b, nc, chunk, h, d).transpose(1, 0, 2, 3, 4)
    pc = (None if q_pos is None
          else q_pos.reshape(b, nc, chunk).transpose(1, 0, 2))

    def step(_, inp):
        q_blk, pos_blk = inp
        return None, jax.checkpoint(block)(q_blk, pos_blk)

    _, out = jax.lax.scan(step, None, (qc, pc))
    return out.transpose(1, 0, 2, 3, 4).reshape(b, s, h, dv)


def _jnp_tri_ij(lam):
    """Paper Table-I map on traced scalars: λ -> (i, j), i >= j."""
    v = 8 * lam + 1
    r = jnp.sqrt(v.astype(jnp.float32)).astype(jnp.int32)
    for _ in range(2):
        r = jnp.where((r + 1) * (r + 1) <= v, r + 1, r)
        r = jnp.where(r * r > v, r - 1, r)
    i = (r - 1) // 2
    return i, lam - i * (i + 1) // 2


def _sdpa_mapped_causal(q, k, v, n_kv_heads, chunk: int = _Q_CHUNK):
    """Causal SDPA over the *mapped triangular block grid* (pure XLA).

    The (q_block i, k_block j) iteration space is enumerated linearly with
    the paper's inverse-triangular map.  Because nb is static, λ -> (i, j)
    is evaluated at TRACE time (numpy!) — the block-pair axis becomes a
    batched dimension with static gather indices, which:
      * computes exactly T(nb)=nb(nb+1)/2 block pairs (no BB waste),
      * is shardable over the tensor axis (`attn_seq` rule) — sequence
        parallelism for heads counts that don't divide the mesh,
      * combines rows with a segment-softmax over the static row ids.
    Exact (fp32 softmax), differentiable, scan-free.
    """
    import numpy as np

    b, s, h, d = q.shape
    dv = v.shape[-1]
    g = h // n_kv_heads
    scale = d ** -0.5
    nb = s // chunk
    assert s % chunk == 0
    npairs = nb * (nb + 1) // 2
    lam = np.arange(npairs)
    i_np = ((np.sqrt(8 * lam + 1).astype(np.int64) - 1) // 2)
    i_np += ((i_np + 2) * (i_np + 1) // 2 <= lam)   # exactness correction
    j_np = lam - i_np * (i_np + 1) // 2
    diag_mask = np.tril(np.ones((chunk, chunk), bool))
    pair_mask = np.where((i_np == j_np)[:, None, None], diag_mask[None],
                         True)                       # (L, C, C) static
    # pad the pair axis to a 16 multiple so it stays shardable on the
    # tensor axis (fully-masked dummy pairs contribute exactly zero)
    pad = (-npairs) % 16
    if pad:
        i_np = np.concatenate([i_np, np.zeros(pad, np.int64)])
        j_np = np.concatenate([j_np, np.zeros(pad, np.int64)])
        pair_mask = np.concatenate(
            [pair_mask, np.zeros((pad, chunk, chunk), bool)], axis=0)

    qg = q.reshape(b, nb, chunk, n_kv_heads, g, d)
    kg = k.reshape(b, nb, chunk, n_kv_heads, d)
    vg = v.reshape(b, nb, chunk, n_kv_heads, dv)
    qp = jnp.take(qg, i_np, axis=1)                 # (B, L, C, kv, g, d)
    kp = jnp.take(kg, j_np, axis=1)
    vp = jnp.take(vg, j_np, axis=1)
    qp = lc(qp, "batch", "attn_seq", None, None, None, None)
    kp = lc(kp, "batch", "attn_seq", None, None, None)
    vp = lc(vp, "batch", "attn_seq", None, None, None)

    logits = jnp.einsum("blskgd,bltkd->blkgst", qp.astype(jnp.float32),
                        kp.astype(jnp.float32)) * scale  # (B,L,kv,g,C,C)
    logits = jnp.where(pair_mask[None, :, None, None, :, :], logits, NEG_INF)

    m_pair = logits.max(axis=-1)                    # (B, L, kv, g, C)
    m_row = jax.ops.segment_max(m_pair.swapaxes(0, 1), i_np,
                                num_segments=nb)    # (nb, B, kv, g, C)
    m_full = jnp.take(m_row, i_np, axis=0).swapaxes(0, 1)
    p = jnp.exp(logits - m_full[..., None])
    l_pair = p.sum(axis=-1)
    l_row = jax.ops.segment_sum(l_pair.swapaxes(0, 1), i_np,
                                num_segments=nb)
    o_pair = jnp.einsum("blkgst,bltkd->blkgsd", p, vp.astype(jnp.float32))
    o_row = jax.ops.segment_sum(o_pair.swapaxes(0, 1), i_np,
                                num_segments=nb)    # (nb, B, kv, g, C, dv)
    out = o_row / jnp.maximum(l_row, 1e-30)[..., None]
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(b, s, h, dv)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------


def gqa_init(key, cfg, dtype):
    ks = jax.random.split(key, 4)
    d, h, hk, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    p = {
        "wq": dense_init(ks[0], d, (h, hd), dtype),
        "wk": dense_init(ks[1], d, (hk, hd), dtype),
        "wv": dense_init(ks[2], d, (hk, hd), dtype),
        "wo": dense_init(ks[3], h * hd, d, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def gqa_specs(cfg):
    s = {
        "wq": (EMBED, HEADS, None),
        "wk": (EMBED, KV_HEADS, None),
        "wv": (EMBED, KV_HEADS, None),
        "wo": (HEADS, EMBED),  # fused (h*hd) input dim — sharded like heads
    }
    if cfg.qk_norm:
        s["q_norm"] = (HEAD_DIM,)
        s["k_norm"] = (HEAD_DIM,)
    return s


def _pallas_causal(q, k, v, grid_mode, block, interpret):
    from repro.kernels.tri_attn.ops import causal_attention

    qt, kt, vt = (x.swapaxes(1, 2) for x in (q, k, v))  # -> (B, H, S, D)
    out = causal_attention(qt, kt, vt, block, block, grid_mode, interpret)
    return out.swapaxes(1, 2)


def gqa_apply(p, cfg, x, *, positions=None, cache=None, cross_kv=None):
    """Returns (out, new_cache). x: (B, S, d)."""
    b, s, _ = x.shape
    h, hk, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = jnp.einsum("bsd,dhe->bshe", x, p["wq"])
    if cross_kv is None:
        k = jnp.einsum("bsd,dhe->bshe", x, p["wk"])
        v = jnp.einsum("bsd,dhe->bshe", x, p["wv"])
    else:  # cross-attention: kv from encoder/vision states
        k = jnp.einsum("btd,dhe->bthe", cross_kv, p["wk"])
        v = jnp.einsum("btd,dhe->bthe", cross_kv, p["wv"])
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    else:
        positions = jnp.broadcast_to(positions, (b, s))
    if cross_kv is None and cfg.rope_theta:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)  # same positions: k is from x
    # attn_seq -> model gives sequence-parallel attention when the head
    # count doesn't divide the tensor axis (divisibility fallback case)
    q = lc(q, "batch", "attn_seq", "heads", None)

    new_cache = None
    q_pos = positions
    if cross_kv is not None:
        q_pos = None                       # rectangular box domain — no mask
    elif cache is not None:                # decode/prefill against cache
        idx = cache["idx"]
        new_cache = {
            **_cache_put(cache, "k", k, idx),
            **_cache_put(cache, "v", v, idx),
            "idx": idx + s,
        }
        k = _cache_get(new_cache, "k", x.dtype)
        v = _cache_get(new_cache, "v", x.dtype)
    k = lc(k, "batch", "kv_seq", "kv_heads", None)
    v = lc(v, "batch", "kv_seq", "kv_heads", None)

    if (cache is None and cross_kv is None
            and cfg.attn_impl in ("pallas_mapped", "pallas_bb")
            and s % cfg.attn_block == 0 and s >= cfg.attn_block):
        grid_mode = "mapped" if cfg.attn_impl == "pallas_mapped" else "bounding_box"
        kr = jnp.repeat(k, h // hk, axis=2) if hk != h else k
        vr = jnp.repeat(v, h // hk, axis=2) if hk != h else v
        out = _pallas_causal(q, kr, vr, grid_mode, cfg.attn_block,
                             cfg.pallas_interpret)
    elif (cache is None and cross_kv is None and cfg.attn_impl == "xla_mapped"
            and s % _Q_CHUNK == 0 and s > _Q_CHUNK):
        out = _sdpa_mapped_causal(q, k, v, hk, _Q_CHUNK)
    else:
        out = _sdpa(q, k, v, hk, q_pos)
    out = lc(out, "batch", None, "heads", None)
    y = jnp.einsum("bshe,hed->bsd", out, p["wo"].reshape(h, hd, cfg.d_model))
    return y, new_cache


def _quantize_rows(t):
    """absmax int8 quantization over the last dim: (values, scales)."""
    scale = jnp.max(jnp.abs(t.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(scale, 1e-8) / 127.0
    q = jnp.round(t.astype(jnp.float32) / scale).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def _cache_put(cache, key, val, idx, ndim4: bool = True):
    """Insert `val` at position idx, quantizing when the cache is int8."""
    store = cache[key]
    if store.dtype == jnp.int8:
        q, scale = _quantize_rows(val)
        start = (0, idx, 0, 0) if ndim4 else (0, idx, 0)
        new = jax.lax.dynamic_update_slice(store, q, start)
        new_s = jax.lax.dynamic_update_slice(
            cache[key + "_scale"], scale, start)
        return {key: new, key + "_scale": new_s}
    start = (0, idx, 0, 0) if ndim4 else (0, idx, 0)
    return {key: jax.lax.dynamic_update_slice(
        store, val.astype(store.dtype), start)}


def _cache_get(entries, key, dtype):
    """Read (dequantize if int8) a cache tensor."""
    t = entries[key]
    if t.dtype == jnp.int8:
        return (t.astype(jnp.float32) * entries[key + "_scale"]).astype(dtype)
    return t


def gqa_cache_init(cfg, batch: int, max_seq: int, dtype):
    hk, hd = cfg.n_kv_heads, cfg.head_dim
    if cfg.kv_cache_quant:
        return {
            "k": jnp.zeros((batch, max_seq, hk, hd), jnp.int8),
            "k_scale": jnp.zeros((batch, max_seq, hk, 1), jnp.float32),
            "v": jnp.zeros((batch, max_seq, hk, hd), jnp.int8),
            "v_scale": jnp.zeros((batch, max_seq, hk, 1), jnp.float32),
            "idx": jnp.zeros((), jnp.int32),
        }
    return {
        "k": jnp.zeros((batch, max_seq, hk, hd), dtype),
        "v": jnp.zeros((batch, max_seq, hk, hd), dtype),
        "idx": jnp.zeros((), jnp.int32),
    }


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2): low-rank compressed KV cache + decoupled RoPE key
# ---------------------------------------------------------------------------


def mla_init(key, cfg, dtype):
    ks = jax.random.split(key, 8)
    d, h = cfg.d_model, cfg.n_heads
    ql, kl = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    return {
        "wdq": dense_init(ks[0], d, ql, dtype),
        "q_norm": jnp.ones((ql,), dtype),
        "wuq": dense_init(ks[1], ql, (h, dn + dr), dtype),
        "wdkv": dense_init(ks[2], d, kl, dtype),
        "kv_norm": jnp.ones((kl,), dtype),
        "wuk": dense_init(ks[3], kl, (h, dn), dtype),
        "wuv": dense_init(ks[4], kl, (h, dv), dtype),
        "wkr": dense_init(ks[5], d, dr, dtype),
        "wo": dense_init(ks[6], h * dv, d, dtype),
    }


def mla_specs(cfg):
    return {
        "wdq": (EMBED, "q_lora"),
        "q_norm": ("q_lora",),
        "wuq": ("q_lora", HEADS, None),
        "wdkv": (EMBED, "kv_lora"),
        "kv_norm": ("kv_lora",),
        "wuk": ("kv_lora", HEADS, None),
        "wuv": ("kv_lora", HEADS, None),
        "wkr": (EMBED, None),
        "wo": (HEADS, EMBED),
    }


def mla_apply(p, cfg, x, *, positions=None, cache=None, cross_kv=None):
    assert cross_kv is None
    b, s, _ = x.shape
    h = cfg.n_heads
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    else:
        positions = jnp.broadcast_to(positions, (b, s))

    q = jnp.einsum("bsl,lhe->bshe",
                   rms_norm(jnp.einsum("bsd,dl->bsl", x, p["wdq"]),
                            p["q_norm"], cfg.norm_eps),
                   p["wuq"])
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = rope(q_rope, positions, cfg.rope_theta, cfg.rope_scaling)
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)   # (B,S,H,dn+dr)
    q_full = lc(q_full, "batch", None, "heads", None)

    ckv = jnp.einsum("bsd,dl->bsl", x, p["wdkv"])          # compressed kv
    krope = rope(jnp.einsum("bsd,dr->bsr", x, p["wkr"])[:, :, None, :],
                 positions, cfg.rope_theta,
                 cfg.rope_scaling)[:, :, 0, :]             # shared rope key

    new_cache = None
    if cache is not None:
        idx = cache["idx"]
        new_cache = {
            **_cache_put(cache, "ckv", ckv, idx, ndim4=False),
            **_cache_put(cache, "krope", krope, idx, ndim4=False),
            "idx": idx + s,
        }
        ckv = _cache_get(new_cache, "ckv", x.dtype)
        krope = _cache_get(new_cache, "krope", x.dtype)
    ckv = lc(ckv, "batch", "kv_seq", "kv_lora")
    ckv_n = rms_norm(ckv, p["kv_norm"], cfg.norm_eps)
    t = ckv_n.shape[1]
    # YaRN's mscale squared rides on the softmax scale, in both paths
    scale = (dn + dr) ** -0.5 * yarn_softmax_scale(cfg.rope_scaling)

    absorb = (cfg.mla_absorb != "never" and cache is not None and s <= 32)
    if absorb:
        # weight absorption (decode): move W_uk onto the query and keep
        # attention in the compressed kv_lora space — the per-step
        # up-projection of the whole cache (2·T·kl·H·(dn+dv) flops) vanishes.
        #   q·k = (W_uk q_nope)·c_kv ;  probs·v = (probs·c_kv)·W_uv
        q_abs = jnp.einsum("bshe,lhe->bshl", q_nope, p["wuk"])
        logits = (
            jnp.einsum("bshl,btl->bhst", q_abs.astype(jnp.float32),
                       ckv_n.astype(jnp.float32))
            + jnp.einsum("bshr,btr->bhst", q_rope.astype(jnp.float32),
                         krope.astype(jnp.float32))
        ) * scale
        idx = new_cache["idx"] - s
        mask = (jnp.arange(t)[None, None, :]
                <= idx + jnp.arange(s)[None, :, None])
        logits = jnp.where(mask[:, None, :, :], logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1)
        o_lat = jnp.einsum("bhst,btl->bshl", probs,
                           ckv_n.astype(jnp.float32))
        out = jnp.einsum("bshl,lhe->bshe", o_lat.astype(x.dtype), p["wuv"])
        y = jnp.einsum("bsf,fd->bsd", out.reshape(b, s, h * dv), p["wo"])
        return y, new_cache

    k_nope = jnp.einsum("btl,lhe->bthe", ckv_n, p["wuk"])  # (B,T,H,dn)
    v = jnp.einsum("btl,lhe->bthe", ckv_n, p["wuv"])       # (B,T,H,dv)
    k_full = jnp.concatenate(
        [k_nope, jnp.broadcast_to(krope[:, :, None, :], (b, t, h, dr))],
        axis=-1)
    k_full = lc(k_full, "batch", "kv_seq", "heads", None)
    v = lc(v, "batch", "kv_seq", "heads", None)

    out = _sdpa(q_full, k_full, v, h, positions, scale=scale)
    out = out.reshape(b, s, h * dv)
    y = jnp.einsum("bsf,fd->bsd", out, p["wo"])
    return y, new_cache


def mla_cache_init(cfg, batch: int, max_seq: int, dtype):
    # int8 quantization is NOT offered for MLA: the compressed latent is
    # already ~14x smaller than a GQA cache, and the rms_norm + up-projection
    # amplify absmax-int8 noise to ~8% logits error (measured) — the
    # compression budget is spent. kv_cache_quant therefore applies to GQA
    # caches only.
    return {
        "ckv": jnp.zeros((batch, max_seq, cfg.kv_lora_rank), dtype),
        "krope": jnp.zeros((batch, max_seq, cfg.qk_rope_dim), dtype),
        "idx": jnp.zeros((), jnp.int32),
    }
