"""Composable model assembly for every architecture in ``configs``.

Families:
  dense / moe — decoder-only transformer (GQA or MLA attention, SwiGLU MLP or
                sort-dispatch MoE), scan-over-layers.  A MoE model with
                ``first_dense_layers`` scans a dense stack
                (``dense_layers``) and then the MoE stack (``layers``),
                each with its own stacked params and cache; serving caches
                of MoE models carry the expert layer's counters
                (``moe_counts``, ``moe.COUNTERS``) summed on the device.
  vlm         — decoder with one gated cross-attention (image) layer per
                ``cross_attn_every``-layer group; patch embeddings are a stub
                input (precomputed, already projected to d_model).
  ssm         — RWKV6 stack (time mix + channel mix), chunked WKV.
  hybrid      — Zamba2: Mamba2 backbone + ONE shared attention/MLP block
                invoked every ``hybrid_attn_every`` layers (weights shared,
                per-invocation KV caches).
  audio       — Whisper-style encoder-decoder; conv frontend is a stub input
                (precomputed frame embeddings).

Every family provides: init_params / param_specs / forward (teacher-forced
logits) / init_cache / prefill / decode_step.  All stacks scan over layers
with stacked params (compile-time + HBM win) and optional remat.

Note: ``jax.lax.scan`` treats ``None`` as an empty pytree, which lets the
cache-less (training) and cached (serving) paths share one scan body.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.distribution.sharding import logical_constraint as lc
from repro.models import attention as attn
from repro.models import mamba2 as m2
from repro.models import moe as moe_mod
from repro.models import rwkv6 as rwkv
from repro.models.common import (
    EMBED, VOCAB, dense_init, embed_init, layer_norm, mlp_init, mlp_specs,
    prepend_layers_axis, rms_norm, stack_layers, swiglu,
)

# ---------------------------------------------------------------------------
# shared small pieces
# ---------------------------------------------------------------------------


def _pol(cfg) -> str:
    """Effective remat policy string for a config."""
    return cfg.remat_policy if cfg.remat else "none"


def _remat(fn, enabled):
    """enabled: bool (legacy) or a ModelConfig-style policy string."""
    policy = enabled if isinstance(enabled, str) else ("full" if enabled else "none")
    if policy == "none":
        return fn
    if policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)  # "full": save only layer boundaries


def _gelu_mlp_init(key, d, f, dtype):
    k1, k2 = jax.random.split(key)
    return {"up": dense_init(k1, d, f, dtype), "down": dense_init(k2, f, d, dtype)}


def _gelu_mlp_specs():
    return {"up": (EMBED, "ffn"), "down": ("ffn", EMBED)}


def _gelu_mlp(p, x):
    return jnp.einsum("bsf,fd->bsd", jax.nn.gelu(
        jnp.einsum("bsd,df->bsf", x, p["up"])), p["down"])


def _attn_init(key, cfg, dtype):
    if cfg.attention_type == "mla":
        return attn.mla_init(key, cfg, dtype)
    return attn.gqa_init(key, cfg, dtype)


def _attn_specs(cfg):
    return attn.mla_specs(cfg) if cfg.attention_type == "mla" else attn.gqa_specs(cfg)


def _attn_apply(p, cfg, x, **kw):
    if cfg.attention_type == "mla":
        return attn.mla_apply(p, cfg, x, **kw)
    return attn.gqa_apply(p, cfg, x, **kw)


def _attn_cache_init(cfg, batch, max_seq, dtype):
    if cfg.attention_type == "mla":
        return attn.mla_cache_init(cfg, batch, max_seq, dtype)
    return attn.gqa_cache_init(cfg, batch, max_seq, dtype)


def _dtype(cfg):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def _stack_cache(one, n: int):
    return jax.tree.map(
        lambda t: jnp.broadcast_to(t[None], (n, *t.shape)).copy(), one)


def _logits(params, cfg, h):
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", h, w,
                        preferred_element_type=jnp.float32)
    return lc(logits, "batch", None, "vocab")


def _embed(params, cfg, tokens):
    x = jnp.take(params["embed"], tokens, axis=0)
    return lc(x, "batch", None, None)


def _bidir_attn(lp_attn, cfg, x):
    """Bidirectional self-attention (whisper encoder — box domain)."""
    b, s, _ = x.shape
    q = jnp.einsum("bsd,dhe->bshe", x, lp_attn["wq"])
    k = jnp.einsum("bsd,dhe->bshe", x, lp_attn["wk"])
    v = jnp.einsum("bsd,dhe->bshe", x, lp_attn["wv"])
    o = attn._sdpa(q, k, v, cfg.n_kv_heads, q_pos=None)
    return jnp.einsum(
        "bshe,hed->bsd", o,
        lp_attn["wo"].reshape(cfg.n_heads, cfg.head_dim, cfg.d_model))


# ===========================================================================
# dense / moe / vlm decoder layers
# ===========================================================================


def _layer_init(key, cfg, dtype, cross: bool = False, moe: bool = True):
    """moe=False: a MoE model's leading dense layer (SwiGLU of d_ff)."""
    k1, k2 = jax.random.split(key)
    p: dict[str, Any] = {
        "ln1": jnp.ones((cfg.d_model,), dtype),
        "ln2": jnp.ones((cfg.d_model,), dtype),
        "attn": attn.gqa_init(k1, cfg, dtype) if cross
        else _attn_init(k1, cfg, dtype),
    }
    if cfg.family == "moe" and moe and not cross:
        p["moe"] = moe_mod.moe_init(k2, cfg, dtype)
    else:
        p["mlp"] = mlp_init(k2, cfg.d_model, cfg.d_ff, dtype)
    if cross:
        p["xattn_gate"] = jnp.zeros((), jnp.float32)
    return p


def _layer_specs(cfg, cross: bool = False, moe: bool = True):
    s: dict[str, Any] = {
        "ln1": (EMBED,), "ln2": (EMBED,),
        "attn": attn.gqa_specs(cfg) if cross else _attn_specs(cfg),
    }
    if cfg.family == "moe" and moe and not cross:
        s["moe"] = moe_mod.moe_specs(cfg)
    else:
        s["mlp"] = mlp_specs()
    if cross:
        s["xattn_gate"] = ()
    return s


def _layer_apply(p, cfg, x, *, positions=None, cache=None, cross_kv=None,
                 with_aux: bool = False):
    """(x, new_cache, aux, counts): aux (the MoE load-balancing loss) only
    with ``with_aux``, counts (``moe.COUNTERS``) only from a MoE layer that
    serves against a cache; None otherwise."""
    cross = cross_kv is not None
    apply = attn.gqa_apply if cross else _attn_apply
    h, new_cache = apply(
        p["attn"], cfg, rms_norm(x, p["ln1"], cfg.norm_eps),
        positions=positions, cache=cache, cross_kv=cross_kv,
    )
    if cross:
        h = h * jnp.tanh(p["xattn_gate"]).astype(h.dtype)
    x = x + h
    inner = rms_norm(x, p["ln2"], cfg.norm_eps)
    aux = jnp.zeros((), jnp.float32) if with_aux else None
    counts = None
    if "moe" in p and cache is not None:
        out, counts = moe_mod.moe_serve(p["moe"], cfg, inner)
        x = x + out
    elif "moe" in p:
        out = moe_mod.moe_apply(p["moe"], cfg, inner, with_aux=with_aux)
        if with_aux:
            out, aux = out
        x = x + out
    else:
        x = x + swiglu(inner, p["mlp"]["gate"], p["mlp"]["up"], p["mlp"]["down"])
    return x, new_cache, aux, counts


def _scan_stack(layers, cfg, x, *, positions, caches, with_aux):
    """Scan one homogeneous stack: (x, new_caches, aux summed or None,
    counts summed over its layers or None)."""
    body = _remat(
        lambda xx, lp, c: _layer_apply(lp, cfg, xx, positions=positions,
                                       cache=c, with_aux=with_aux),
        _pol(cfg),
    )

    def f(xx, lp_c):
        lp, c = lp_c
        xx, nc, aux, counts = body(xx, lp, c)
        return xx, (nc, aux, counts)

    x, (ncs, aux, counts) = jax.lax.scan(f, x, (layers, caches))
    return (x, ncs, None if aux is None else jnp.sum(aux),
            None if counts is None else jnp.sum(counts, axis=0))


def _decoder_stack(params, cfg, x, *, positions=None, cache=None,
                   cross_states=None, with_aux: bool = False):
    """Scan over layers: (x, new_cache, aux).  ``cache`` is the whole
    decoder cache or None (both work; new_cache is None without one);
    with_aux additionally sums the MoE load-balancing loss (else None).
    """
    if cfg.family != "vlm":
        stacks = (["dense_layers"] if cfg.first_dense_layers else []) \
            + ["layers"]
        new_cache = None if cache is None else dict(cache)
        aux_total = None
        for name in stacks:
            x, ncs, aux, counts = _scan_stack(
                params[name], cfg, x, positions=positions,
                caches=None if cache is None else cache[name],
                with_aux=with_aux)
            if new_cache is not None:
                new_cache[name] = ncs
            if counts is not None:
                new_cache["moe_counts"] = cache["moe_counts"] + counts
            if aux is not None:
                aux_total = aux if aux_total is None else aux_total + aux
        return x, new_cache, aux_total

    # vlm: groups of (cross_attn_every - 1) self layers + 1 cross layer
    body = _remat(
        lambda xx, lp, c: _layer_apply(lp, cfg, xx, positions=positions,
                                       cache=c)[:2],
        _pol(cfg),
    )
    cross_body = _remat(
        lambda xx, lp, c: _layer_apply(lp, cfg, xx, positions=positions,
                                       cache=c, cross_kv=cross_states)[:2],
        _pol(cfg),
    )

    def group_fn(xx, gp_gc):
        gp, gc = gp_gc
        self_caches = None if gc is None else gc["self"]

        def self_fn(x_in, lp_c):
            lp, c = lp_c
            return body(x_in, lp, c)

        xx, new_self = jax.lax.scan(self_fn, xx, (gp["self"], self_caches))
        xx, _ = cross_body(xx, gp["cross"], None)
        return xx, (None if gc is None else {"self": new_self})

    x, new_groups = jax.lax.scan(
        group_fn, x,
        (params["groups"], None if cache is None else cache["layers"]))
    return x, (None if cache is None else {"layers": new_groups}), None


# ===========================================================================
# RWKV6 (ssm)
# ===========================================================================


def _rwkv_layer_init(key, cfg, dtype):
    k1, k2 = jax.random.split(key)
    return {
        "ln1": jnp.ones((cfg.d_model,), dtype),
        "ln2": jnp.ones((cfg.d_model,), dtype),
        "tmix": rwkv.rwkv_block_init(k1, cfg, dtype),
        "cmix": rwkv.rwkv_cmix_init(k2, cfg, dtype),
    }


def _rwkv_layer_specs(cfg):
    return {
        "ln1": (EMBED,), "ln2": (EMBED,),
        "tmix": rwkv.rwkv_block_specs(cfg),
        "cmix": rwkv.rwkv_cmix_specs(cfg),
    }


def _rwkv_layer_apply(p, cfg, x, state):
    """state: dict(tmix_x, cmix_x, wkv). Chunked when seq allows, else scan."""
    use_scan = (x.shape[1] % 64 != 0)
    n1 = rms_norm(x, p["ln1"], cfg.norm_eps)
    if use_scan:
        o, last_x, wkv = rwkv.rwkv_mix_scan(p["tmix"], cfg, n1,
                                            state["tmix_x"], state["wkv"])
    else:
        o, last_x, wkv = rwkv.rwkv_mix_chunked(p["tmix"], cfg, n1,
                                               state["tmix_x"], state["wkv"])
    x = x + o
    n2 = rms_norm(x, p["ln2"], cfg.norm_eps)
    o2, last_c = rwkv.rwkv_cmix_apply(p["cmix"], cfg, n2, state["cmix_x"])
    x = x + o2
    return x, {"tmix_x": last_x, "cmix_x": last_c, "wkv": wkv}


def _rwkv_zero_state(cfg, batch):
    h = cfg.rwkv_heads
    hd = cfg.d_model // h
    return {
        "tmix_x": jnp.zeros((batch, cfg.d_model), jnp.float32),
        "cmix_x": jnp.zeros((batch, cfg.d_model), jnp.float32),
        "wkv": jnp.zeros((batch, h, hd, hd), jnp.float32),
    }


def _rwkv_stack(params, cfg, x, caches=None):
    zero = None if caches is not None else _rwkv_zero_state(cfg, x.shape[0])
    body = _remat(
        lambda xx, lp, st: _rwkv_layer_apply(lp, cfg, xx, st), _pol(cfg))

    def f(xx, lp_c):
        lp, c = lp_c
        out, ns = body(xx, lp, c if c is not None else zero)
        return out, (ns if c is not None else None)

    return jax.lax.scan(f, x, (params["layers"], caches))


# ===========================================================================
# Zamba2 hybrid
# ===========================================================================


def _zamba_shared_init(key, cfg, dtype):
    k1, k2 = jax.random.split(key)
    return {
        "ln1": jnp.ones((cfg.d_model,), dtype),
        "ln2": jnp.ones((cfg.d_model,), dtype),
        "attn": attn.gqa_init(k1, cfg, dtype),
        "mlp": mlp_init(k2, cfg.d_model, cfg.d_ff, dtype),
    }


def _zamba_shared_specs(cfg):
    return {
        "ln1": (EMBED,), "ln2": (EMBED,),
        "attn": attn.gqa_specs(cfg),
        "mlp": mlp_specs(),
    }


def _zamba_shared_apply(p, cfg, x, positions=None, cache=None):
    h, nc = attn.gqa_apply(p["attn"], cfg, rms_norm(x, p["ln1"], cfg.norm_eps),
                           positions=positions, cache=cache)
    x = x + h
    x = x + swiglu(rms_norm(x, p["ln2"], cfg.norm_eps), p["mlp"]["gate"],
                   p["mlp"]["up"], p["mlp"]["down"])
    return x, nc


def _hybrid_stack(params, cfg, x, *, positions=None, cache=None):
    """Scan over mamba layers; fire the shared attn block every `period`.

    cache: dict(mamba_state(L,...), conv_tail(L,...), shared{k,v}(n_inv,...),
    idx) or None.  Returns (x, new_cache_or_None).
    """
    period = cfg.hybrid_attn_every
    shared = params["shared"]
    bsz = x.shape[0]
    h, ph, n = cfg.mamba_heads, cfg.mamba_d_inner // cfg.mamba_heads, cfg.ssm_state
    idx = None if cache is None else cache["idx"]

    mamba_body = _remat(
        lambda xx, lp, st, tl: m2.mamba2_apply(
            lp["mamba"], cfg, rms_norm(xx, lp["ln"], cfg.norm_eps), state=st,
            conv_tail=tl),
        _pol(cfg))
    shared_plain = _remat(
        lambda xx: _zamba_shared_apply(shared, cfg, xx, positions=positions)[0],
        _pol(cfg))

    def f(carry, inp):
        xx, shared_kv = carry
        lp, lidx, mstate, ctail = inp
        if mstate is None:
            mstate = jnp.zeros((bsz, h, ph, n), jnp.float32)
        hh, new_state, new_tail = mamba_body(xx, lp, mstate, ctail)
        xx = xx + hh
        fire = (lidx % period) == (period - 1)
        if shared_kv is None:  # training: no cache
            xx = jax.lax.cond(fire, shared_plain, lambda a: a, xx)
            return (xx, None), (None, None)
        inv = lidx // period

        def fire_fn(args):
            xx_, kv = args
            c = {"k": kv["k"][inv], "v": kv["v"][inv], "idx": idx}
            out, nc = _zamba_shared_apply(shared, cfg, xx_,
                                          positions=positions, cache=c)
            kv = {"k": kv["k"].at[inv].set(nc["k"]),
                  "v": kv["v"].at[inv].set(nc["v"])}
            return out, kv

        xx, shared_kv = jax.lax.cond(fire, fire_fn, lambda a: a,
                                     (xx, shared_kv))
        return (xx, shared_kv), (new_state, new_tail)

    if cache is None:
        (x, _), _ = jax.lax.scan(
            f, (x, None),
            (params["layers"], jnp.arange(cfg.n_layers), None, None))
        return x, None

    (x, new_shared), (new_states, new_tails) = jax.lax.scan(
        f, (x, {"k": cache["shared"]["k"], "v": cache["shared"]["v"]}),
        (params["layers"], jnp.arange(cfg.n_layers), cache["mamba_state"],
         cache["conv_tail"]))
    new_cache = {
        "mamba_state": new_states,
        "conv_tail": new_tails,
        "shared": new_shared,
        "idx": idx + x.shape[1],
    }
    return x, new_cache


# ===========================================================================
# Whisper (audio)
# ===========================================================================


def _whisper_enc_layer_init(key, cfg, dtype):
    k1, k2 = jax.random.split(key)
    return {
        "ln1_w": jnp.ones((cfg.d_model,), dtype),
        "ln1_b": jnp.zeros((cfg.d_model,), dtype),
        "ln2_w": jnp.ones((cfg.d_model,), dtype),
        "ln2_b": jnp.zeros((cfg.d_model,), dtype),
        "attn": attn.gqa_init(k1, cfg, dtype),
        "mlp": _gelu_mlp_init(k2, cfg.d_model, cfg.d_ff, dtype),
    }


def _whisper_enc_layer_specs(cfg):
    return {
        "ln1_w": (EMBED,), "ln1_b": (EMBED,),
        "ln2_w": (EMBED,), "ln2_b": (EMBED,),
        "attn": attn.gqa_specs(cfg),
        "mlp": _gelu_mlp_specs(),
    }


def _whisper_dec_layer_init(key, cfg, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "ln1_w": jnp.ones((cfg.d_model,), dtype),
        "ln1_b": jnp.zeros((cfg.d_model,), dtype),
        "lnx_w": jnp.ones((cfg.d_model,), dtype),
        "lnx_b": jnp.zeros((cfg.d_model,), dtype),
        "ln2_w": jnp.ones((cfg.d_model,), dtype),
        "ln2_b": jnp.zeros((cfg.d_model,), dtype),
        "attn": attn.gqa_init(k1, cfg, dtype),
        "xattn": attn.gqa_init(k2, cfg, dtype),
        "mlp": _gelu_mlp_init(k3, cfg.d_model, cfg.d_ff, dtype),
    }


def _whisper_dec_layer_specs(cfg):
    return {
        "ln1_w": (EMBED,), "ln1_b": (EMBED,),
        "lnx_w": (EMBED,), "lnx_b": (EMBED,),
        "ln2_w": (EMBED,), "ln2_b": (EMBED,),
        "attn": attn.gqa_specs(cfg),
        "xattn": attn.gqa_specs(cfg),
        "mlp": _gelu_mlp_specs(),
    }


def _whisper_encode(params, cfg, frames):
    """frames: (B, T_enc, d) stub embeddings -> encoder states."""
    x = frames + params["enc_pos"][None, : frames.shape[1], :]

    def enc_layer(xx, lp):
        n1 = layer_norm(xx, lp["ln1_w"], lp["ln1_b"])
        xx = xx + _bidir_attn(lp["attn"], cfg, n1)
        n2 = layer_norm(xx, lp["ln2_w"], lp["ln2_b"])
        xx = xx + _gelu_mlp(lp["mlp"], n2)
        return xx, None

    enc_layer = _remat(enc_layer, _pol(cfg))
    x, _ = jax.lax.scan(enc_layer, x, params["enc_layers"])
    return layer_norm(x, params["enc_norm_w"], params["enc_norm_b"])


def _whisper_dec_stack(params, cfg, x, enc_states, *, positions=None,
                       caches=None, cross_cache=None):
    """enc_states for full fwd/prefill; cross_cache {k,v}(L,...) for decode."""

    def dec_layer(xx, lp, c, xk, xv):
        h, nc = attn.gqa_apply(
            lp["attn"], cfg, layer_norm(xx, lp["ln1_w"], lp["ln1_b"]),
            positions=positions, cache=c)
        xx = xx + h
        nx = layer_norm(xx, lp["lnx_w"], lp["lnx_b"])
        if xk is not None:  # decode: cached per-layer cross k/v
            q = jnp.einsum("bsd,dhe->bshe", nx, lp["xattn"]["wq"])
            o = attn._sdpa(q, xk, xv, cfg.n_kv_heads, q_pos=None)
            xx = xx + jnp.einsum(
                "bshe,hed->bsd", o,
                lp["xattn"]["wo"].reshape(cfg.n_heads, cfg.head_dim,
                                          cfg.d_model))
        else:
            h2, _ = attn.gqa_apply(lp["xattn"], cfg, nx, cross_kv=enc_states)
            xx = xx + h2
        n2 = layer_norm(xx, lp["ln2_w"], lp["ln2_b"])
        xx = xx + _gelu_mlp(lp["mlp"], n2)
        return xx, nc

    dec_layer = _remat(dec_layer, _pol(cfg))

    def f(xx, inp):
        lp, c, xk, xv = inp
        return dec_layer(xx, lp, c, xk, xv)

    xk = None if cross_cache is None else cross_cache["k"]
    xv = None if cross_cache is None else cross_cache["v"]
    return jax.lax.scan(f, x, (params["dec_layers"], caches, xk, xv))


def _whisper_logits(params, cfg, x):
    h = layer_norm(x, params["final_norm"], params["final_norm_b"])
    logits = jnp.einsum("bsd,dv->bsv", h, params["lm_head"],
                        preferred_element_type=jnp.float32)
    return lc(logits, "batch", None, "vocab")


# ===========================================================================
# Public API
# ===========================================================================


@functools.partial(jax.jit, static_argnums=1)
def init_params(key, cfg):
    """Random parameters for ``cfg``, built in one jitted program: its peak
    is the parameter bytes.  Run eagerly, the f32 samples of ``dense_init``
    and the per-layer arrays ``stack_layers`` copies from sit beside the
    result, about twice the bytes (a 32-layer 6B model would not fit one
    16 GB chip)."""
    dtype = _dtype(cfg)
    if cfg.family in ("dense", "moe", "vlm"):
        k1, k2, k3 = jax.random.split(key, 3)
        params: dict[str, Any] = {
            "embed": embed_init(k1, cfg.padded_vocab, cfg.d_model, dtype),
            "final_norm": jnp.ones((cfg.d_model,), dtype),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = dense_init(k2, cfg.d_model, cfg.padded_vocab,
                                           dtype)
        if cfg.family == "vlm":
            n_groups = cfg.n_layers // cfg.cross_attn_every
            per_group_self = cfg.cross_attn_every - 1
            params["groups"] = stack_layers(
                lambda k: {
                    "self": stack_layers(
                        lambda kk: _layer_init(kk, cfg, dtype), k,
                        per_group_self),
                    "cross": _layer_init(jax.random.fold_in(k, 7), cfg, dtype,
                                         cross=True),
                }, k3, n_groups)
        else:
            # layer i draws from key i of the split whichever stack holds it
            keys = jax.random.split(k3, cfg.n_layers)
            nd = cfg.first_dense_layers
            if nd:
                params["dense_layers"] = jax.vmap(
                    lambda k: _layer_init(k, cfg, dtype, moe=False))(keys[:nd])
            params["layers"] = jax.vmap(
                lambda k: _layer_init(k, cfg, dtype))(keys[nd:])
        return params
    if cfg.family == "ssm":
        k1, k2, k3 = jax.random.split(key, 3)
        return {
            "embed": embed_init(k1, cfg.padded_vocab, cfg.d_model, dtype),
            "final_norm": jnp.ones((cfg.d_model,), dtype),
            "lm_head": dense_init(k2, cfg.d_model, cfg.padded_vocab, dtype),
            "layers": stack_layers(
                lambda k: _rwkv_layer_init(k, cfg, dtype), k3, cfg.n_layers),
        }
    if cfg.family == "hybrid":
        k1, k2, k3, k4 = jax.random.split(key, 4)
        return {
            "embed": embed_init(k1, cfg.padded_vocab, cfg.d_model, dtype),
            "final_norm": jnp.ones((cfg.d_model,), dtype),
            "lm_head": dense_init(k2, cfg.d_model, cfg.padded_vocab, dtype),
            "layers": stack_layers(
                lambda k: {"ln": jnp.ones((cfg.d_model,), dtype),
                           "mamba": m2.mamba2_init(k, cfg, dtype)},
                k3, cfg.n_layers),
            "shared": _zamba_shared_init(k4, cfg, dtype),
        }
    if cfg.family == "audio":
        ks = jax.random.split(key, 6)
        return {
            "embed": embed_init(ks[0], cfg.padded_vocab, cfg.d_model, dtype),
            "enc_pos": embed_init(ks[1], cfg.encoder_seq, cfg.d_model, dtype),
            "dec_pos": embed_init(ks[2], cfg.max_seq, cfg.d_model, dtype),
            "enc_layers": stack_layers(
                lambda k: _whisper_enc_layer_init(k, cfg, dtype), ks[3],
                cfg.encoder_layers),
            "dec_layers": stack_layers(
                lambda k: _whisper_dec_layer_init(k, cfg, dtype), ks[4],
                cfg.decoder_layers),
            "enc_norm_w": jnp.ones((cfg.d_model,), dtype),
            "enc_norm_b": jnp.zeros((cfg.d_model,), dtype),
            "final_norm": jnp.ones((cfg.d_model,), dtype),
            "final_norm_b": jnp.zeros((cfg.d_model,), dtype),
            "lm_head": dense_init(ks[5], cfg.d_model, cfg.padded_vocab, dtype),
        }
    raise ValueError(cfg.family)


def param_specs(cfg):
    if cfg.family in ("dense", "moe", "vlm"):
        specs: dict[str, Any] = {
            "embed": (VOCAB, EMBED), "final_norm": (EMBED,),
        }
        if not cfg.tie_embeddings:
            specs["lm_head"] = (EMBED, VOCAB)
        if cfg.family == "vlm":
            specs["groups"] = prepend_layers_axis({
                "self": prepend_layers_axis(_layer_specs(cfg)),
                "cross": _layer_specs(cfg, cross=True),
            })
        else:
            if cfg.first_dense_layers:
                specs["dense_layers"] = prepend_layers_axis(
                    _layer_specs(cfg, moe=False))
            specs["layers"] = prepend_layers_axis(_layer_specs(cfg))
        return specs
    if cfg.family == "ssm":
        return {
            "embed": (VOCAB, EMBED), "final_norm": (EMBED,),
            "lm_head": (EMBED, VOCAB),
            "layers": prepend_layers_axis(_rwkv_layer_specs(cfg)),
        }
    if cfg.family == "hybrid":
        return {
            "embed": (VOCAB, EMBED), "final_norm": (EMBED,),
            "lm_head": (EMBED, VOCAB),
            "layers": prepend_layers_axis(
                {"ln": (EMBED,), "mamba": m2.mamba2_specs(cfg)}),
            "shared": _zamba_shared_specs(cfg),
        }
    if cfg.family == "audio":
        return {
            "embed": (VOCAB, EMBED),
            "enc_pos": ("frames", EMBED), "dec_pos": (None, EMBED),
            "enc_layers": prepend_layers_axis(_whisper_enc_layer_specs(cfg)),
            "dec_layers": prepend_layers_axis(_whisper_dec_layer_specs(cfg)),
            "enc_norm_w": (EMBED,), "enc_norm_b": (EMBED,),
            "final_norm": (EMBED,), "final_norm_b": (EMBED,),
            "lm_head": (EMBED, VOCAB),
        }
    raise ValueError(cfg.family)


def forward(params, cfg, tokens, extra=None, positions=None,
            with_aux: bool = False):
    """Teacher-forced logits (B, S, padded_vocab) fp32.

    with_aux=True returns (logits, moe_aux_loss) — aux is 0 for non-MoE.
    """
    aux = jnp.zeros((), jnp.float32)
    if cfg.family == "audio":
        enc_states = _whisper_encode(params, cfg, extra)
        x = _embed(params, cfg, tokens)
        x = x + params["dec_pos"][None, : tokens.shape[1], :]
        x, _ = _whisper_dec_stack(params, cfg, x, enc_states,
                                  positions=positions)
        out = _whisper_logits(params, cfg, x)
        return (out, aux) if with_aux else out
    x = _embed(params, cfg, tokens)
    if cfg.family in ("dense", "moe", "vlm"):
        collect = with_aux and cfg.family == "moe"
        x, _, moe_aux = _decoder_stack(params, cfg, x, positions=positions,
                                       cross_states=extra, with_aux=collect)
        if collect:
            aux = moe_aux
    elif cfg.family == "ssm":
        x, _ = _rwkv_stack(params, cfg, x)
    elif cfg.family == "hybrid":
        x, _ = _hybrid_stack(params, cfg, x, positions=positions)
    else:
        raise ValueError(cfg.family)
    out = _logits(params, cfg, x)
    return (out, aux) if with_aux else out


def init_cache(cfg, batch: int, max_seq: int, extra_len: int = 0):
    dtype = _dtype(cfg)
    if cfg.family in ("dense", "moe"):
        one = _attn_cache_init(cfg, batch, max_seq, dtype)
        nd = cfg.first_dense_layers
        cache = {"layers": _stack_cache(one, cfg.n_layers - nd)}
        if nd:
            cache["dense_layers"] = _stack_cache(one, nd)
        if cfg.family == "moe":
            cache["moe_counts"] = jnp.zeros((len(moe_mod.COUNTERS),),
                                            jnp.int32)
        return cache
    if cfg.family == "vlm":
        n_groups = cfg.n_layers // cfg.cross_attn_every
        per_group_self = cfg.cross_attn_every - 1
        one = _attn_cache_init(cfg, batch, max_seq, dtype)
        return {"layers": _stack_cache(
            {"self": _stack_cache(one, per_group_self)}, n_groups)}
    if cfg.family == "ssm":
        return {"layers": _stack_cache(_rwkv_zero_state(cfg, batch),
                                       cfg.n_layers)}
    if cfg.family == "hybrid":
        h = cfg.mamba_heads
        ph, n = cfg.mamba_d_inner // h, cfg.ssm_state
        w = cfg.mamba_conv_width
        n_inv = cfg.n_layers // cfg.hybrid_attn_every
        # shared-block cache stays unquantized (tiny; _hybrid_stack slices
        # k/v per invocation explicitly)
        shared_one = attn.gqa_cache_init(
            cfg.replace(kv_cache_quant=False) if cfg.kv_cache_quant else cfg,
            batch, max_seq, dtype)
        return {
            "mamba_state": jnp.zeros((cfg.n_layers, batch, h, ph, n),
                                     jnp.float32),
            "conv_tail": jnp.zeros(
                (cfg.n_layers, batch, w - 1, cfg.mamba_d_inner + 2 * n), dtype),
            "shared": {"k": _stack_cache(shared_one["k"], n_inv),
                       "v": _stack_cache(shared_one["v"], n_inv)},
            "idx": jnp.zeros((), jnp.int32),
        }
    if cfg.family == "audio":
        one = _attn_cache_init(cfg, batch, max_seq, dtype)
        hk, hd = cfg.n_kv_heads, cfg.head_dim
        return {
            "layers": _stack_cache(one, cfg.decoder_layers),
            "cross": {
                "k": jnp.zeros((cfg.decoder_layers, batch, extra_len, hk, hd),
                               dtype),
                "v": jnp.zeros((cfg.decoder_layers, batch, extra_len, hk, hd),
                               dtype),
            },
        }
    raise ValueError(cfg.family)


def prefill(params, cfg, tokens, extra=None, cache=None):
    """Fill the cache with a teacher-forced pass; returns (logits, cache)."""
    b, s = tokens.shape
    if cache is None:
        cache = init_cache(cfg, b, cfg.max_seq,
                           extra.shape[1] if extra is not None else 0)
    positions = jnp.arange(s)[None, :]
    if cfg.family == "audio":
        enc_states = _whisper_encode(params, cfg, extra)

        def xkv(lp):
            k = jnp.einsum("btd,dhe->bthe", enc_states, lp["xattn"]["wk"])
            v = jnp.einsum("btd,dhe->bthe", enc_states, lp["xattn"]["wv"])
            return k.astype(_dtype(cfg)), v.astype(_dtype(cfg))

        ks, vs = jax.vmap(xkv)(params["dec_layers"])
        cross = {"k": ks, "v": vs}
        x = _embed(params, cfg, tokens) + params["dec_pos"][None, :s, :]
        x, new_l = _whisper_dec_stack(params, cfg, x, None,
                                      positions=positions,
                                      caches=cache["layers"],
                                      cross_cache=cross)
        return _whisper_logits(params, cfg, x), {"layers": new_l,
                                                 "cross": cross}
    x = _embed(params, cfg, tokens)
    if cfg.family in ("dense", "moe", "vlm"):
        x, new_cache, _ = _decoder_stack(params, cfg, x, positions=positions,
                                         cache=cache, cross_states=extra)
        return _logits(params, cfg, x), new_cache
    if cfg.family == "ssm":
        x, new_l = _rwkv_stack(params, cfg, x, caches=cache["layers"])
        return _logits(params, cfg, x), {"layers": new_l}
    if cfg.family == "hybrid":
        x, new_c = _hybrid_stack(params, cfg, x, positions=positions,
                                 cache=cache)
        return _logits(params, cfg, x), new_c
    raise ValueError(cfg.family)


def decode_step(params, cfg, token, cache, extra=None):
    """token: (B, 1); one serving step against the cache."""
    b = token.shape[0]
    if cfg.family in ("dense", "moe", "vlm", "audio"):
        idx = _cache_idx(cfg, cache)
        positions = jnp.broadcast_to(idx[None, None], (b, 1))
        if cfg.family == "audio":
            x = _embed(params, cfg, token)
            x = x + jnp.take(params["dec_pos"], positions, axis=0)
            x, new_l = _whisper_dec_stack(
                params, cfg, x, None, positions=positions,
                caches=cache["layers"], cross_cache=cache["cross"])
            return _whisper_logits(params, cfg, x), {
                "layers": new_l, "cross": cache["cross"]}
        x = _embed(params, cfg, token)
        x, new_cache, _ = _decoder_stack(params, cfg, x, positions=positions,
                                         cache=cache, cross_states=extra)
        return _logits(params, cfg, x), new_cache
    x = _embed(params, cfg, token)
    if cfg.family == "ssm":
        x, new_l = _rwkv_stack(params, cfg, x, caches=cache["layers"])
        return _logits(params, cfg, x), {"layers": new_l}
    if cfg.family == "hybrid":
        positions = jnp.broadcast_to(cache["idx"][None, None], (b, 1))
        x, new_c = _hybrid_stack(params, cfg, x, positions=positions,
                                 cache=cache)
        return _logits(params, cfg, x), new_c
    raise ValueError(cfg.family)


def _cache_idx(cfg, cache):
    if cfg.family in ("dense", "moe", "audio"):
        return cache["layers"]["idx"][0]
    if cfg.family == "vlm":
        return cache["layers"]["self"]["idx"][0, 0]
    raise ValueError(cfg.family)
