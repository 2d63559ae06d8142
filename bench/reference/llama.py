"""Plain float32 forward of a Llama-architecture decoder (RMSNorm, RoPE,
grouped-query causal attention, SwiGLU), the reference for served tokens.

The weights are made again here from the configuration's recipe (the same
random draws, in the served dtype), one layer at a time inside one jitted
program per layer, so that the whole model never sits on the device at
float32.  Every matrix product runs at ``Precision.HIGHEST``.

``token_gaps`` reads, at each position whose token the server chose, how far
that token's reference logit lies below the reference's best.  With
``control=True`` it also reads the same gap for the token that a
weight-only float8 (e4m3, per-output-channel scale) copy of the same model
puts first: the precision step below bfloat16 that the check has to catch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0  # largest finite float8_e4m3fn


def dims(cfg: dict) -> dict:
    vocab = cfg["vocab_size"]
    return {
        "d": cfg["hidden_size"], "h": cfg["num_attention_heads"],
        "hk": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
        "ff": cfg["intermediate_size"], "layers": cfg["num_hidden_layers"],
        "vocab": vocab, "padded_vocab": -(-vocab // 128) * 128,
        "eps": cfg["rms_norm_eps"], "theta": cfg["rope_theta"],
        "dtype": jnp.bfloat16 if cfg["torch_dtype"] == "bfloat16"
        else jnp.float32,
    }


def _served(w, dt):
    """A weight as the server holds it (its dtype), read back as float32."""
    return w.astype(dt).astype(jnp.float32)


def _trunc(key, shape, fan_in, dt):
    w = jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) \
        * (1.0 / np.sqrt(fan_in))
    return _served(w, dt)


def _fp8(w, axis):
    """Weight-only float8 e4m3 with one scale per slice along ``axis``
    (the reduction axis of the product: one scale per output channel)."""
    amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / FP8_MAX
    return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _keys(n_layers: int, key: int):
    k_embed, k_head, k_layers = jax.random.split(jax.random.PRNGKey(key), 3)
    return k_embed, k_head, jax.random.split(k_layers, n_layers)


def _layer_weights(key, m):
    ka, kb = jax.random.split(key)
    ks = jax.random.split(ka, 4)
    d, h, hk, hd, ff, dt = m["d"], m["h"], m["hk"], m["hd"], m["ff"], \
        m["dtype"]
    km = jax.random.split(kb, 3)
    return {
        "wq": _trunc(ks[0], (d, h, hd), d, dt),
        "wk": _trunc(ks[1], (d, hk, hd), d, dt),
        "wv": _trunc(ks[2], (d, hk, hd), d, dt),
        "wo": _trunc(ks[3], (h * hd, d), h * hd, dt),
        "gate": _trunc(km[0], (d, ff), d, dt),
        "up": _trunc(km[1], (d, ff), d, dt),
        "down": _trunc(km[2], (ff, d), ff, dt),
    }


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, theta):
    """Rotary embedding of (B, S, heads, hd) at positions 0..S-1, the
    first and second halves of each head rotated as pairs."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, w, m):
    b, s, _ = x.shape
    h, hk, hd = m["h"], m["hk"], m["hd"]
    a = _rms(x, m["eps"])
    q = _rope(jnp.einsum("bsd,dhe->bshe", a, w["wq"], precision=HIGHEST),
              m["theta"])
    k = _rope(jnp.einsum("bsd,dhe->bshe", a, w["wk"], precision=HIGHEST),
              m["theta"])
    v = jnp.einsum("bsd,dhe->bshe", a, w["wv"], precision=HIGHEST)
    # query head j reads key/value head j // (h // hk)
    qg = q.reshape(b, s, hk, h // hk, hd)
    logits = jnp.einsum("bskgd,btkd->bkgst", qg, k,
                        precision=HIGHEST) / np.sqrt(hd)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    logits = jnp.where(causal, logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bkgst,btkd->bskgd", p, v,
                   precision=HIGHEST).reshape(b, s, h * hd)
    x = x + jnp.dot(o, w["wo"], precision=HIGHEST)
    a = _rms(x, m["eps"])
    g = jax.nn.silu(jnp.dot(a, w["gate"], precision=HIGHEST))
    u = jnp.dot(a, w["up"], precision=HIGHEST)
    return x + jnp.dot(g * u, w["down"], precision=HIGHEST)


@functools.partial(jax.jit, static_argnames=("mk", "control"))
def _embed(tokens, key, mk, control):
    m = dict(mk)
    table = _served(jax.random.normal(
        key, (m["padded_vocab"], m["d"]), jnp.float32) * 0.02, m["dtype"])
    x = jnp.take(table, tokens, axis=0)
    xc = jnp.take(_fp8(table, 1), tokens, axis=0) if control else x
    return x, xc


@functools.partial(jax.jit, static_argnames=("mk", "control"))
def _block(x, xc, key, mk, control):
    m = dict(mk)
    w = _layer_weights(key, m)
    x = _layer(x, w, m)
    if control:
        wc = {name: _fp8(t, 0) for name, t in w.items()}
        xc = _layer(xc, wc, m)
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
    the float8 copy's first choices) below the reference's best logit.

    ``prompts`` (B, P) and ``served`` (B, N) are token ids: the forward runs
    over the prompt and the first N - 1 served tokens, and position P - 1 + i
    is where served token i was chosen."""
    m = dims(cfg)
    mk = tuple(sorted(m.items()))
    seq = np.concatenate([prompts, served[:, :-1]], axis=1).astype(np.int32)
    k_embed, k_head, k_layers = _keys(m["layers"], cfg["weights"]["key"])
    x, xc = _embed(jnp.asarray(seq), k_embed, mk, control)
    for i in range(m["layers"]):
        x, xc = _block(x, xc, k_layers[i], mk, control)
    p = prompts.shape[1]
    gap, gap_ctl = _gaps(x[:, p - 1:], xc[:, p - 1:],
                         jnp.asarray(served, jnp.int32), k_head, mk, control)
    gap = np.asarray(gap)
    return gap, (np.asarray(gap_ctl) if control else None)
