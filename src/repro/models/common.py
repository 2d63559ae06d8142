"""Parameter plumbing shared by every model: init helpers and logical-axis
spec trees (nested dicts mirroring the param trees; leaves are tuples of
logical axis names consumed by distribution/sharding.py)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# Logical axis names (mapped to mesh axes by distribution/sharding.py)
BATCH = "batch"
SEQ = "seq"
EMBED = "embed"          # d_model — FSDP-sharded on weights
FFN = "ffn"              # hidden ffn dim — TP-sharded
HEADS = "heads"          # q heads — TP-sharded
KV_HEADS = "kv_heads"    # kv heads — replicated when < TP degree
HEAD_DIM = "head_dim"
VOCAB = "vocab"          # TP-sharded
EXPERTS = "experts"      # EP-sharded
LAYERS = "layers"        # scan axis — never sharded
STATE = "state"          # ssm state dim
CAP = "capacity"


def dense_init(key, in_dim: int, out_dims, dtype, scale: float | None = None):
    """Truncated-normal init for a (in, *out) projection, fan-in scaled."""
    out_dims = (out_dims,) if isinstance(out_dims, int) else tuple(out_dims)
    if scale is None:
        scale = 1.0 / np.sqrt(in_dim)
    w = jax.random.truncated_normal(
        key, -2.0, 2.0, (in_dim, *out_dims), jnp.float32
    ) * scale
    return w.astype(dtype)


def embed_init(key, vocab: int, d: int, dtype):
    w = jax.random.normal(key, (vocab, d), jnp.float32) * 0.02
    return w.astype(dtype)


def rms_norm(x, weight, eps: float = 1e-6):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(dt) * weight


def layer_norm(x, weight, bias, eps: float = 1e-5):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = xf.mean(axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps)).astype(dt) * weight + bias


def swiglu(x, w_gate, w_up, w_down):
    """SwiGLU MLP: down( silu(x@gate) * (x@up) )."""
    g = jax.nn.silu(jnp.einsum("...d,df->...f", x, w_gate))
    u = jnp.einsum("...d,df->...f", x, w_up)
    return jnp.einsum("...f,fd->...d", g * u, w_down)


def mlp_init(key, d_model: int, d_ff: int, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "gate": dense_init(k1, d_model, d_ff, dtype),
        "up": dense_init(k2, d_model, d_ff, dtype),
        "down": dense_init(k3, d_ff, d_model, dtype),
    }


def mlp_specs():
    return {"gate": (EMBED, FFN), "up": (EMBED, FFN), "down": (FFN, EMBED)}


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * np.log(factor) + 1.0


def yarn_softmax_scale(scaling) -> float:
    """The factor YaRN puts on the attention softmax scale (DeepSeek's
    ``mscale_all_dim``, applied to q and k alike: squared)."""
    if scaling is None:
        return 1.0
    return _yarn_mscale(scaling.factor, scaling.mscale_all_dim) ** 2


def _yarn_ramp(half: int, theta: float, scaling) -> np.ndarray:
    """Per frequency, the share taken from the interpolated (÷ factor)
    frequency: 0 below the correction dim of ``beta_fast`` rotations over
    the original context, 1 above that of ``beta_slow``, linear between."""
    dim = 2 * half

    def corr(rot):
        return dim * np.log(scaling.original_max_position
                            / (rot * 2 * np.pi)) / (2 * np.log(theta))

    low = max(np.floor(corr(scaling.beta_fast)), 0)
    high = min(np.ceil(corr(scaling.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    return np.clip((np.arange(half) - low) / (high - low), 0, 1
                   ).astype(np.float32)


def rope(x, positions, theta: float = 10000.0, scaling=None):
    """Rotary embedding over the last dim of (..., seq, n_heads, head_dim);
    ``scaling`` (a ``configs.base.Yarn``) blends in YaRN's frequencies."""
    head_dim = x.shape[-1]
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if scaling is not None:
        ramp = _yarn_ramp(half, theta, scaling)
        freqs = freqs / scaling.factor * ramp + freqs * (1 - ramp)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., seq, half)
    cos = jnp.cos(angles)[..., :, None, :]  # broadcast over heads
    sin = jnp.sin(angles)[..., :, None, :]
    if scaling is not None:
        amp = (_yarn_mscale(scaling.factor, scaling.mscale)
               / _yarn_mscale(scaling.factor, scaling.mscale_all_dim))
        cos, sin = cos * amp, sin * amp
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1
    ).astype(x.dtype)


def stack_layers(init_fn, key, n_layers: int):
    """Init n_layers instances with their leaves on a leading `layers` axis
    (vmapped: one program whatever the depth, the same values as a loop)."""
    return jax.vmap(init_fn)(jax.random.split(key, n_layers))


def prepend_layers_axis(spec_tree):
    """Add the scan `layers` axis in front of every leaf spec."""
    return jax.tree.map(
        lambda s: (LAYERS, *s),
        spec_tree,
        is_leaf=lambda s: isinstance(s, tuple),
    )


def count_params(params) -> int:
    return int(sum(np.prod(p.shape) for p in jax.tree.leaves(params)))
