"""The main path's kernels compile for a TPU v5e — no chip attached.

The TPU compiler is installed with jaxlib's TPU plugin and compiles for a
*described* topology, refusing what the chip's compiler would refuse
(unsupported primitives, misaligned blocks, too much VMEM).  Interpret-mode
tests cannot see any of that.  The topology is described inside a fixture,
never at import: only one process may load the TPU library at a time, and
every pytest worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.domain_map import ops
from repro.kernels.domain_map.kernel import (
    build_map_call, build_membership_call,
)
from repro.kernels.tri_attn.ops import causal_attention

N_POINTS = 1 << 20
#: bounding boxes of exactly 2^20 cells, by domain dimension
BOXES = {2: (1024, 1024), 3: (64, 128, 128), 5: (16, 16, 16, 16, 16)}
DOMAINS = ("tri2d", "pyramid3d", "msimplex5", "carpet2d")


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with JAX's persistent compilation
    cache off: entries written for a described chip cannot be read back."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _compiles_to_kernel(compiled) -> None:
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("name", DOMAINS)
def test_map_kernel_compiles_for_v5e(one_chip, name):
    _, padded, ndigits = ops.map_plan(name, N_POINTS, 1024)
    call = build_map_call(name, padded, 1024, ndigits, interpret=False)
    _compiles_to_kernel(
        jax.jit(call, out_shardings=one_chip).lower().compile())


@pytest.mark.parametrize("name", DOMAINS)
def test_membership_kernel_compiles_for_v5e(one_chip, name):
    from repro.core.domains import DOMAINS as ALL

    extent = BOXES[ALL[name].dim]
    _, padded, ndigits = ops.membership_plan(name, extent, 1024)
    call = build_membership_call(name, extent, 1024, ndigits,
                                 interpret=False, padded_total=padded)
    _compiles_to_kernel(
        jax.jit(call, out_shardings=one_chip).lower().compile())


@pytest.mark.parametrize("grid_mode", ("mapped", "bounding_box"))
def test_tri_attn_compiles_for_v5e(one_chip, grid_mode):
    qkv = jax.ShapeDtypeStruct((1, 32, 4096, 128), jnp.bfloat16,
                               sharding=one_chip)
    fn = jax.jit(lambda q, k, v: causal_attention(q, k, v, 128, 128,
                                                  grid_mode, False))
    _compiles_to_kernel(fn.lower(qkv, qkv, qkv).compile())
