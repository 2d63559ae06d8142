"""Plain float32 forward of DeepSeek-V3 (one chip's expert-parallel share),
the reference for served tokens.

The layer equations, after arXiv:2412.19437 and the published config:

* RMSNorm (weights 1 as drawn) before attention and before the FFN;
* MLA, expanded: q = W_uq rms(W_dq x), split into nope and rope parts;
  c = rms(W_dkv x), k_nope = W_uk c, v = W_uv c, a shared rope key W_kr x;
  scores (q_nope k_nope + q_rope k_rope) x scale, causal softmax, W_o;
  scale = (nope + rope)^-1/2 x (0.1 ln(factor) mscale_all_dim + 1)^2;
* YaRN RoPE (rotate-half pairs): each inverse frequency θ^(-2i/d) blended
  with itself ÷ factor by the linear ramp between the correction dims of
  beta_fast and beta_slow rotations over the original context;
* the first ``first_k_dense_replace`` layers: SwiGLU of ``intermediate_size``;
* the rest: noaux_tc routing over all ``deployment.router_experts`` experts
  (sigmoid scores, the choice on scores + bias, the best ``topk_group`` of
  ``n_group`` groups by the sum of their two best, top-k; weights are the
  chosen scores renormalised x ``routed_scaling_factor``); only the held
  experts' SwiGLUs contribute here, plus the shared expert on every token.

The weights are drawn again from the configuration's recipe, one layer at a
time inside one jitted program per layer, so that the whole model never
sits on the device at float32.  Every matrix product runs at
``Precision.HIGHEST``.  ``token_gaps`` is ``llama.token_gaps``'s reading:
how far each served token's reference logit lies below the best, and with
``control=True`` the same for a weight-only float8 (e4m3) copy's choices.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.llama import HIGHEST, _fp8, _served, _trunc


def dims(cfg: dict) -> dict:
    vocab = cfg["vocab_size"]
    rs = cfg["rope_scaling"]
    dep = cfg["deployment"]
    return {
        "d": cfg["hidden_size"], "h": cfg["num_attention_heads"],
        "ql": cfg["q_lora_rank"], "kl": cfg["kv_lora_rank"],
        "dn": cfg["qk_nope_head_dim"], "dr": cfg["qk_rope_head_dim"],
        "dv": cfg["v_head_dim"], "ff": cfg["intermediate_size"],
        "eff": cfg["moe_intermediate_size"],
        "shared": cfg["n_shared_experts"],
        "layers": cfg["num_hidden_layers"],
        "dense": cfg["first_k_dense_replace"],
        "experts": dep["router_experts"], "held": cfg["n_routed_experts"],
        "k": cfg["num_experts_per_tok"], "groups": cfg["n_group"],
        "keep_groups": cfg["topk_group"],
        "scaling": cfg["routed_scaling_factor"],
        "norm_topk": cfg["norm_topk_prob"],
        "vocab": vocab, "padded_vocab": -(-vocab // 128) * 128,
        "eps": cfg["rms_norm_eps"], "theta": cfg["rope_theta"],
        "factor": rs["factor"], "orig": rs["original_max_position_embeddings"],
        "beta_fast": rs["beta_fast"], "beta_slow": rs["beta_slow"],
        "mscale": rs["mscale"], "mscale_all_dim": rs["mscale_all_dim"],
        "dtype": jnp.bfloat16 if cfg["torch_dtype"] == "bfloat16"
        else jnp.float32,
    }


def _keys(n_layers: int, key: int):
    k_embed, k_head, k_layers = jax.random.split(jax.random.PRNGKey(key), 3)
    return k_embed, k_head, jax.random.split(k_layers, n_layers)


def _mla_weights(key, m):
    d, h, ql, kl = m["d"], m["h"], m["ql"], m["kl"]
    dn, dr, dv, dt = m["dn"], m["dr"], m["dv"], m["dtype"]
    ks = jax.random.split(key, 8)
    return {
        "wdq": _trunc(ks[0], (d, ql), d, dt),
        "wuq": _trunc(ks[1], (ql, h, dn + dr), ql, dt),
        "wdkv": _trunc(ks[2], (d, kl), d, dt),
        "wuk": _trunc(ks[3], (kl, h, dn), kl, dt),
        "wuv": _trunc(ks[4], (kl, h, dv), kl, dt),
        "wkr": _trunc(ks[5], (d, dr), d, dt),
        "wo": _trunc(ks[6], (h * dv, d), h * dv, dt),
    }


def _swiglu_weights(key, d, ff, dt):
    km = jax.random.split(key, 3)
    return {"gate": _trunc(km[0], (d, ff), d, dt),
            "up": _trunc(km[1], (d, ff), d, dt),
            "down": _trunc(km[2], (ff, d), ff, dt)}


def _expert_weights(key, m, in_dim, out_dim):
    """(held, in, out): expert j drawn from key j of the split over all."""
    keys = jax.random.split(key, m["experts"])[:m["held"]]
    return jnp.stack([_trunc(k, (in_dim, out_dim), in_dim, m["dtype"])
                      for k in keys])


def _layer_weights(key, m, dense: bool):
    k_attn, k_ffn = jax.random.split(key)
    w = {"attn": _mla_weights(k_attn, m)}
    d, dt = m["d"], m["dtype"]
    if dense:
        w["mlp"] = _swiglu_weights(k_ffn, d, m["ff"], dt)
        return w
    ks = jax.random.split(k_ffn, 5)
    w["router"] = jax.random.truncated_normal(
        ks[0], -2.0, 2.0, (d, m["experts"]), jnp.float32) / np.sqrt(d)
    w["bias"] = jnp.zeros((m["experts"],), jnp.float32)
    w["gate"] = _expert_weights(ks[1], m, d, m["eff"])
    w["up"] = _expert_weights(ks[2], m, d, m["eff"])
    w["down"] = _expert_weights(ks[3], m, m["eff"], d)
    w["shared"] = _swiglu_weights(ks[4], d, m["eff"] * m["shared"], dt)
    return w


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _yarn_freqs(m):
    dim = m["dr"]
    base = m["theta"] ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    extra, inter = 1.0 / base, 1.0 / (m["factor"] * base)

    def corr(rot):
        return dim * math.log(m["orig"] / (rot * 2 * math.pi)) \
            / (2 * math.log(m["theta"]))

    low = max(math.floor(corr(m["beta_fast"])), 0)
    high = min(math.ceil(corr(m["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return jnp.asarray(inter * ramp + extra * (1 - ramp), jnp.float32)


def _rope(x, m):
    """YaRN rotary embedding of (B, S, heads, dr) at positions 0..S-1, the
    halves of each head rotated as pairs."""
    half = x.shape[-1] // 2
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * _yarn_freqs(m)
    amp = _mscale(m["factor"], m["mscale"]) \
        / _mscale(m["factor"], m["mscale_all_dim"])
    cos = (jnp.cos(ang) * amp)[None, :, None, :]
    sin = (jnp.sin(ang) * amp)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mla(a, w, m):
    b, s, _ = a.shape
    h, dn, dr, dv = m["h"], m["dn"], m["dr"], m["dv"]
    cq = _rms(jnp.dot(a, w["wdq"], precision=HIGHEST), m["eps"])
    q = jnp.einsum("bsl,lhe->bshe", cq, w["wuq"], precision=HIGHEST)
    q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], m)
    c = _rms(jnp.dot(a, w["wdkv"], precision=HIGHEST), m["eps"])
    k_nope = jnp.einsum("btl,lhe->bthe", c, w["wuk"], precision=HIGHEST)
    v = jnp.einsum("btl,lhe->bthe", c, w["wuv"], precision=HIGHEST)
    k_rope = _rope(jnp.dot(a, w["wkr"], precision=HIGHEST)[:, :, None, :], m)
    scale = (dn + dr) ** -0.5 * _mscale(m["factor"], m["mscale_all_dim"]) ** 2
    logits = (jnp.einsum("bshe,bthe->bhst", q_nope, k_nope, precision=HIGHEST)
              + jnp.einsum("bshe,btge->bhst", q_rope, k_rope,
                           precision=HIGHEST)) * scale
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    p = jax.nn.softmax(jnp.where(causal, logits, -jnp.inf), axis=-1)
    o = jnp.einsum("bhst,bthe->bshe", p, v, precision=HIGHEST)
    return jnp.dot(o.reshape(b, s, h * dv), w["wo"], precision=HIGHEST)


def _swiglu(a, w):
    g = jax.nn.silu(jnp.dot(a, w["gate"], precision=HIGHEST))
    u = jnp.dot(a, w["up"], precision=HIGHEST)
    return jnp.dot(g * u, w["down"], precision=HIGHEST)


def route(logits, bias, m):
    """noaux_tc: (weights (..., E) zero off the chosen experts)."""
    e, g = logits.shape[-1], m["groups"]
    s = jax.nn.sigmoid(logits)
    choice = s + bias
    groups = choice.reshape(*choice.shape[:-1], g, e // g)
    best2 = jnp.sort(groups, axis=-1)[..., -2:].sum(-1)
    kth = jnp.sort(best2, axis=-1)[..., -m["keep_groups"]][..., None]
    kept = jnp.repeat(best2 >= kth, e // g, axis=-1)
    choice = jnp.where(kept, choice, -jnp.inf)
    kth = jnp.sort(choice, axis=-1)[..., -m["k"]][..., None]
    chosen = choice >= kth
    w = jnp.where(chosen, s, 0.0)
    if m["norm_topk"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return w * m["scaling"]


def _moe(a, w, m):
    """The held experts' part of the routed output, plus the shared
    expert's."""
    weights = route(jnp.dot(a, w["router"], precision=HIGHEST), w["bias"], m)
    out = _swiglu(a, w["shared"])
    for j in range(m["held"]):
        expert = {n: w[n][j] for n in ("gate", "up", "down")}
        out = out + weights[..., j:j + 1] * _swiglu(a, expert)
    return out


def _layer(x, w, m):
    x = x + _mla(_rms(x, m["eps"]), w["attn"], m)
    a = _rms(x, m["eps"])
    return x + (_swiglu(a, w["mlp"]) if "mlp" in w else _moe(a, w, m))


def _control_weights(w):
    """The float8 copy: every matrix weight-only e4m3 with one scale per
    output channel (the router too), over the product's reduction axis: the
    first, or the second of a stack of experts; the bias stays as it is."""
    def one(path, t):
        if t.ndim < 2:
            return t
        stacked = t.ndim == 3 and path[-1].key in ("gate", "up", "down")
        return _fp8(t, 1 if stacked else 0)
    return jax.tree_util.tree_map_with_path(one, w)


@functools.partial(jax.jit, static_argnames=("mk", "control"))
def _embed(tokens, key, mk, control):
    m = dict(mk)
    table = _served(jax.random.normal(
        key, (m["padded_vocab"], m["d"]), jnp.float32) * 0.02, m["dtype"])
    x = jnp.take(table, tokens, axis=0)
    xc = jnp.take(_fp8(table, 1), tokens, axis=0) if control else x
    return x, xc


@functools.partial(jax.jit, static_argnames=("mk", "dense", "control"))
def _block(x, xc, key, mk, dense, control):
    m = dict(mk)
    w = _layer_weights(key, m, dense)
    x = _layer(x, w, m)
    if control:
        xc = _layer(xc, _control_weights(w), m)
    return x, xc


@functools.partial(jax.jit, static_argnames=("mk", "control"))
def _gaps(x, xc, targets, key, mk, control):
    m = dict(mk)
    head = _trunc(key, (m["d"], m["padded_vocab"]), m["d"], m["dtype"])
    logits = jnp.dot(_rms(x, m["eps"]), head,
                     precision=HIGHEST)[..., :m["vocab"]]
    best = logits.max(-1)
    gap = best - jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    if not control:
        return gap, gap
    lc = jnp.dot(_rms(xc, m["eps"]), _fp8(head, 0),
                 precision=HIGHEST)[..., :m["vocab"]]
    top = jnp.argmax(lc, -1)
    return gap, best - jnp.take_along_axis(logits, top[..., None], -1)[..., 0]


def token_gaps(cfg: dict, prompts: np.ndarray, served: np.ndarray,
               control: bool = False) -> tuple[np.ndarray, np.ndarray | None]:
    """Gaps (B, n_served) of the served tokens (and, with ``control``, of
    the float8 copy's first choices) below the reference's best logit;
    as ``llama.token_gaps``."""
    m = dims(cfg)
    mk = tuple(sorted(m.items()))
    seq = np.concatenate([prompts, served[:, :-1]], axis=1).astype(np.int32)
    k_embed, k_head, k_layers = _keys(m["layers"], cfg["weights"]["key"])
    x, xc = _embed(jnp.asarray(seq), k_embed, mk, control)
    for i in range(m["layers"]):
        x, xc = _block(x, xc, k_layers[i], mk, i < m["dense"], control)
    p = prompts.shape[1]
    gap, gap_ctl = _gaps(x[:, p - 1:], xc[:, p - 1:],
                         jnp.asarray(served, jnp.int32), k_head, mk, control)
    gap = np.asarray(gap)
    return gap, (np.asarray(gap_ctl) if control else None)
