"""ShapeDtypeStruct stand-ins + logical shardings for every dry-run cell.

``input_specs(cfg, shape)`` builds the exact abstract inputs each step kind
lowers against (no device allocation), with NamedShardings attached so
``jax.jit(...).lower(*specs)`` sees the production distribution:

  train_*    -> train_step(params, opt_state, batch)
  prefill_*  -> prefill(params, tokens, extra)
  decode_* / long_* -> decode_step(params, token, cache, extra)
       (one new token against a seq_len KV cache)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.configs.shapes import ShapeSpec
from repro.distribution import sharding as shd
from repro.models import transformer as T
from repro.train import optimizer as opt


# ---------------------------------------------------------------------------
# activation rule sets per shape kind
# ---------------------------------------------------------------------------


def act_rules_for(shape: ShapeSpec) -> dict:
    if shape.kind == "train" or shape.kind == "prefill":
        return dict(shd.ACT_RULES)
    if shape.name == "long_500k":  # batch=1: sequence parallelism instead
        return {**shd.ACT_RULES, "batch": None, "kv_seq": ("data", "model")}
    # decode: shard the cache's sequence dim over the tensor axis
    return {**shd.ACT_RULES, "kv_seq": "model"}


# ---------------------------------------------------------------------------
# logical spec trees for caches (mirrors transformer.init_cache)
# ---------------------------------------------------------------------------


def _gqa_cache_specs(quant: bool = False):
    s = {"k": ("layers", "batch", "kv_seq", "kv_heads", None),
         "v": ("layers", "batch", "kv_seq", "kv_heads", None),
         "idx": ("layers",)}
    if quant:
        s["k_scale"] = ("layers", "batch", "kv_seq", "kv_heads", None)
        s["v_scale"] = ("layers", "batch", "kv_seq", "kv_heads", None)
    return s


def _mla_cache_specs(quant: bool = False):
    # MLA caches never quantize (see attention.mla_cache_init)
    return {"ckv": ("layers", "batch", "kv_seq", "kv_lora"),
            "krope": ("layers", "batch", "kv_seq", None),
            "idx": ("layers",)}


def cache_specs(cfg: ModelConfig):
    if cfg.family in ("dense", "moe"):
        inner = (_mla_cache_specs(cfg.kv_cache_quant)
                 if cfg.attention_type == "mla"
                 else _gqa_cache_specs(cfg.kv_cache_quant))
        specs = {"layers": inner}
        if cfg.first_dense_layers:
            specs["dense_layers"] = inner
        if cfg.family == "moe":
            specs["moe_counts"] = (None,)
        return specs
    if cfg.family == "vlm":
        base = _gqa_cache_specs(cfg.kv_cache_quant)
        return {"layers": {"self": {
            k: ("layers", *v) for k, v in base.items()}}}
    if cfg.family == "ssm":
        return {"layers": {
            "tmix_x": ("layers", "batch", "embed"),
            "cmix_x": ("layers", "batch", "embed"),
            "wkv": ("layers", "batch", "heads", None, None),
        }}
    if cfg.family == "hybrid":
        # shared attention cache stays unquantized (see transformer.init_cache)
        shared = {"k": ("layers", "batch", "kv_seq", "kv_heads", None),
                  "v": ("layers", "batch", "kv_seq", "kv_heads", None)}
        return {
            "mamba_state": ("layers", "batch", "heads", None, None),
            "conv_tail": ("layers", "batch", None, "ffn"),
            "shared": shared,
            "idx": (),
        }
    if cfg.family == "audio":
        return {
            "layers": _gqa_cache_specs(cfg.kv_cache_quant),
            "cross": {"k": ("layers", "batch", "frames", "kv_heads", None),
                      "v": ("layers", "batch", "frames", "kv_heads", None)},
        }
    raise ValueError(cfg.family)


# ---------------------------------------------------------------------------
# abstract inputs
# ---------------------------------------------------------------------------


def _sds(tree_shapes, tree_specs, mesh, rules):
    """(shape-tree, logical-spec-tree) -> ShapeDtypeStructs with shardings.

    Maps over the SPEC tree first (is_leaf=tuple) so that scalar specs ``()``
    are treated as leaves, not empty containers.
    """

    def one(s, t):
        spec = shd.resolve_spec(s, rules, mesh, t.shape)
        return jax.ShapeDtypeStruct(
            t.shape, t.dtype, sharding=jax.sharding.NamedSharding(mesh, spec))

    return jax.tree.map(one, tree_specs, tree_shapes,
                        is_leaf=lambda s: isinstance(s, tuple))


def _extra_shape(cfg: ModelConfig, batch: int):
    dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    if cfg.family == "vlm":
        return jax.ShapeDtypeStruct((batch, cfg.vision_seq, cfg.d_model), dt)
    if cfg.family == "audio":
        return jax.ShapeDtypeStruct((batch, cfg.encoder_seq, cfg.d_model), dt)
    return None


def params_specs_sds(cfg: ModelConfig, mesh, rules=None):
    rules = rules or shd.PARAM_RULES
    shapes = jax.eval_shape(
        lambda k: T.init_params(k, cfg), jax.random.PRNGKey(0))
    logical = T.param_specs(cfg)
    return _sds(shapes, logical, mesh, rules)


def input_specs(cfg: ModelConfig, shape: ShapeSpec, mesh, tcfg=None,
                rules=None):
    """Returns (fn, tuple_of_abstract_args, donate_argnums) for the cell."""
    rules = rules or act_rules_for(shape)
    params = params_specs_sds(cfg, mesh)
    b, s = shape.global_batch, shape.seq_len
    tok_spec = shd.resolve_spec(("batch", None), rules, mesh, (b, s))
    tok_sharding = jax.sharding.NamedSharding(mesh, tok_spec)
    extra = _extra_shape(cfg, b)
    if extra is not None:
        e_spec = shd.resolve_spec(("batch", None, None), rules, mesh,
                                  extra.shape)
        extra = jax.ShapeDtypeStruct(
            extra.shape, extra.dtype,
            sharding=jax.sharding.NamedSharding(mesh, e_spec))

    if shape.kind == "train":
        from repro.train.train_step import TrainConfig, make_train_step

        opt_shapes = jax.eval_shape(opt.init_state, params)
        opt_logical = opt.state_specs(T.param_specs(cfg))
        opt_sds = _sds(opt_shapes, opt_logical, mesh, shd.PARAM_RULES)
        batch = {
            "tokens": jax.ShapeDtypeStruct((b, s), jnp.int32,
                                           sharding=tok_sharding),
            "labels": jax.ShapeDtypeStruct((b, s), jnp.int32,
                                           sharding=tok_sharding),
        }
        if extra is not None:
            batch["extra"] = extra
        step = make_train_step(cfg, tcfg or TrainConfig())
        return step, (params, opt_sds, batch), (0, 1)

    if shape.kind == "prefill":
        tokens = jax.ShapeDtypeStruct((b, s), jnp.int32, sharding=tok_sharding)

        def fn(p, t, e):
            logits, cache = T.prefill(p, cfg, t, e)
            return logits[:, -1, :], cache

        return fn, (params, tokens, extra), ()

    # decode: one token against a seq_len cache
    extra_len = 0
    if cfg.family == "audio":
        extra_len = cfg.encoder_seq
    cache_shapes = jax.eval_shape(
        lambda: T.init_cache(cfg, b, s, extra_len))
    cache_sds = _sds(cache_shapes, cache_specs(cfg), mesh, rules)
    tok1_spec = shd.resolve_spec(("batch", None), rules, mesh, (b, 1))
    token = jax.ShapeDtypeStruct(
        (b, 1), jnp.int32,
        sharding=jax.sharding.NamedSharding(mesh, tok1_spec))
    dec_extra = extra if cfg.family == "vlm" else None

    def fn(p, t, c, e):
        logits, new_cache = T.decode_step(p, cfg, t, c, e)
        return logits, new_cache

    return fn, (params, token, cache_sds, dec_extra), (2,)
