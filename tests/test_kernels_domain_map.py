"""domain_map Pallas kernels vs oracles, across all six domains."""
import numpy as np
import pytest

from repro.core.domains import DOMAINS
from repro.kernels.domain_map.ops import bb_membership, block_counts, map_coordinates
from repro.kernels.domain_map.ref import bb_membership_ref, map_coordinates_ref

ALL = sorted(DOMAINS)


@pytest.mark.parametrize("dom", ALL)
@pytest.mark.parametrize("n", [1024, 4096])
def test_map_kernel_matches_ref(dom, n):
    got = map_coordinates(dom, n, block_n=1024, interpret=True)
    np.testing.assert_array_equal(got, map_coordinates_ref(dom, n))


@pytest.mark.parametrize("dom,ext", [
    ("tri2d", (64, 64)),
    ("gasket2d", (64, 64)),
    ("carpet2d", (81, 81)),
    ("pyramid3d", (16, 16, 16)),
    ("sierpinski3d", (16, 16, 16)),
    ("menger3d", (27, 27, 27)),
])
def test_membership_kernel_matches_ref(dom, ext):
    got = bb_membership(dom, ext, block_n=1024, interpret=True)
    np.testing.assert_array_equal(got, bb_membership_ref(dom, ext))


@pytest.mark.parametrize("dom", ALL)
def test_membership_counts_match_domain_size(dom):
    """Valid cells in a full-level bounding box == |domain| at that level."""
    d = DOMAINS[dom]
    if d.kind == "dense":
        pytest.skip("box of a dense domain is not a full level")
    level = 4 if d.base <= 4 else (2 if d.base < 20 else 2)
    ext = (d.scale ** level,) * d.dim
    mask = bb_membership(dom, ext, block_n=1024, interpret=True)
    assert int(mask.sum()) == d.size(level)


def test_block_counts_paper_scale():
    bc = block_counts("tri2d", 500_000_000)
    assert bc["mapped_steps"] == 1_953_125
    assert bc["waste_fraction"] > 0.4


def test_pyramid3d_kernel_seed_exact_at_every_tetrahedral_boundary():
    """The in-kernel tetrahedral-root seed (exp/log, since Mosaic has no
    cbrt) plus its correction ladder lands on the numpy tier's coordinates
    at every layer boundary T(z)-1, T(z), T(z)+1 up to 2^21."""
    import jax.numpy as jnp

    from repro.core.maps import np_map
    from repro.kernels.domain_map.geometry import pyramid3d_coords

    z = np.arange(0, 400, dtype=np.int64)
    tet = z * (z + 1) * (z + 2) // 6
    lams = np.unique(np.concatenate([tet - 1, tet, tet + 1]))
    lams = lams[(lams >= 0) & (lams <= 1 << 21)]
    assert lams[-1] > (1 << 21) - 25_000  # the last layer below 2^21 is in
    got = np.stack([np.asarray(a) for a in pyramid3d_coords(
        jnp.asarray(lams, jnp.int32), 0)], axis=-1)
    np.testing.assert_array_equal(got.astype(np.int64),
                                  np_map("pyramid3d", lams))
