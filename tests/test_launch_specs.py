"""launch/specs structural guarantees: every arch x shape must produce
abstract inputs whose spec trees mirror the real runtime structures."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCH_IDS, SHAPES, applicable, get_config, get_smoke_config
from repro.launch.specs import cache_specs
from repro.models import transformer as T


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_cache_specs_mirror_init_cache(arch):
    cfg = get_smoke_config(arch)
    extra_len = cfg.encoder_seq if cfg.family == "audio" else 0
    cache = jax.eval_shape(lambda: T.init_cache(cfg, 2, 64, extra_len))
    specs = cache_specs(cfg)
    assert (jax.tree.structure(cache)
            == jax.tree.structure(specs,
                                  is_leaf=lambda s: isinstance(s, tuple)))
    jax.tree.map(
        lambda s, c: None if len(s) == c.ndim
        else pytest.fail(f"{arch}: spec {s} vs cache shape {c.shape}"),
        specs, cache, is_leaf=lambda s: isinstance(s, tuple))


@pytest.mark.parametrize("arch", ARCH_IDS)
@pytest.mark.parametrize("shape_name", sorted(SHAPES))
def test_input_specs_trace_on_tiny_mesh(arch, shape_name):
    """Abstract-eval every (arch, shape) step on a 1x1 mesh — catches
    structural breakage without the 512-device compile."""
    from repro.distribution import sharding as shd
    from repro.launch.specs import input_specs

    shape = SHAPES[shape_name]
    cfg = get_config(arch).replace(max_seq=shape.seq_len)
    ok, _ = applicable(cfg, shape)
    if not ok:
        pytest.skip("documented long_500k skip")
    # full configs are too big to trace quickly here; shrink to smoke dims
    # but keep the family/shape structure (batch/seq from the real shape
    # spec would explode eval_shape memory on CPU — scale them down too).
    smoke = get_smoke_config(arch)
    cfg = smoke.replace(max_seq=256)
    import dataclasses

    shape = dataclasses.replace(shape, seq_len=256, global_batch=4)
    mesh = shd.make_mesh((1, 1), ("data", "model"))
    with shd.use_sharding(mesh):
        fn, args, donate = input_specs(cfg, shape, mesh)
        out = jax.eval_shape(fn, *args)  # abstract eval only
    assert out is not None
