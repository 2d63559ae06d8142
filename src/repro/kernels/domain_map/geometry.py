"""In-kernel (Pallas-traceable) geometry tiers, registered per domain.

Each domain registers two tiers into the MapRegistry:

  pallas      ``f(lam_block, ndigits) -> [axis arrays]`` — the vectorized
              Table-I map evaluated on a VMEM block of linear indices
              (integer VPU ops only, no gathers),
  membership  ``f(axes, ndigits) -> bool mask`` — the bounding-box kernel's
              discard condition.

All digit→vector tables are evaluated arithmetically (no gathers): e.g. the
Menger digit d maps to the row-major cell index by skipping the 7 void cells
with an ascending ``cell += (cell >= void)`` ladder.  Adding a new geometry
to the kernels is the same one-file registration pattern as the scalar tiers
in ``core/maps``.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core import msimplex as ms
from repro.core.domains import (
    EMBEDDED_FRACTAL_DOMAINS, MENGER_VOIDS, MSIMPLEX_MS,
)
from repro.core.registry import register_map

_MENGER_VOID_CELLS = sorted(9 * x + 3 * y + z for x, y, z in MENGER_VOIDS)


def _vec_isqrt(v):
    """Exact vectorized isqrt for int32 v (fp32 seed + correction ladder)."""
    r = jnp.sqrt(v.astype(jnp.float32)).astype(jnp.int32)
    for _ in range(3):
        r = jnp.where((r + 1) * (r + 1) <= v, r + 1, r)
        r = jnp.where(r * r > v, r - 1, r)
    return jnp.maximum(r, 0)


def _tri_xy(lam):
    x = (_vec_isqrt(8 * lam + 1) - 1) // 2
    return x, lam - x * (x + 1) // 2


def _tet_z(lam):
    """Largest z with T(z) = z(z+1)(z+2)/6 <= lam.  The fp32 cube-root seed
    is exp(log(6 lam) / 3): Mosaic has no ``cbrt``; the ladder makes it
    exact."""
    z = jnp.exp(jnp.log(6.0 * lam.astype(jnp.float32)) / 3.0).astype(jnp.int32)
    for _ in range(3):
        z = jnp.where((z + 1) * (z + 2) * (z + 3) // 6 <= lam, z + 1, z)
        z = jnp.where((z > 0) & (z * (z + 1) * (z + 2) // 6 > lam), z - 1, z)
    return jnp.maximum(z, 0)


# ---------------------------------------------------------------------------
# Dense domains
# ---------------------------------------------------------------------------


@register_map("tri2d", "analytical", tier="pallas")
def tri2d_coords(lam, ndigits):
    del ndigits
    x, y = _tri_xy(lam)
    return [x, y]


@register_map("tri2d", "analytical", tier="membership")
def tri2d_membership(axes, ndigits):
    del ndigits
    x, y = axes
    return y <= x


@register_map("pyramid3d", "analytical", tier="pallas")
def pyramid3d_coords(lam, ndigits):
    del ndigits
    z = _tet_z(lam)
    rem = lam - z * (z + 1) * (z + 2) // 6
    x, y = _tri_xy(rem)
    return [x, y, z]


@register_map("pyramid3d", "analytical", tier="membership")
def pyramid3d_membership(axes, ndigits):
    del ndigits
    x, y, z = axes
    return (y <= x) & (x <= z)


# ---------------------------------------------------------------------------
# Fractal domains
# ---------------------------------------------------------------------------


@register_map("gasket2d", "bitwise", tier="pallas")
def gasket2d_coords(lam, ndigits):
    x = jnp.zeros_like(lam)
    y = jnp.zeros_like(lam)
    m, s = lam, 1
    for _ in range(ndigits):
        d = m % 3
        x += jnp.where(d == 1, s, 0)
        y += jnp.where(d == 2, s, 0)
        m, s = m // 3, s * 2
    return [x, y]


@register_map("gasket2d", "bitwise", tier="membership")
def gasket2d_membership(axes, ndigits):
    del ndigits
    x, y = axes
    return (x & y) == 0


@register_map("carpet2d", "bitwise", tier="pallas")
def carpet2d_coords(lam, ndigits):
    x = jnp.zeros_like(lam)
    y = jnp.zeros_like(lam)
    m, s = lam, 1
    for _ in range(ndigits):
        d = m % 8
        cell = d + (d >= 4).astype(jnp.int32)   # skip the (1,1) void
        x += (cell // 3) * s
        y += (cell % 3) * s
        m, s = m // 8, s * 3
    return [x, y]


@register_map("carpet2d", "bitwise", tier="membership")
def carpet2d_membership(axes, ndigits):
    x, y = axes
    ok = jnp.ones(x.shape, dtype=bool)
    for _ in range(ndigits):
        ok &= ~((x % 3 == 1) & (y % 3 == 1))
        x, y = x // 3, y // 3
    return ok


@register_map("sierpinski3d", "bitwise", tier="pallas")
def sierpinski3d_coords(lam, ndigits):
    x = jnp.zeros_like(lam)
    y = jnp.zeros_like(lam)
    z = jnp.zeros_like(lam)
    m, s = lam, 1
    for _ in range(ndigits):
        d = m % 4
        x += jnp.where(d == 1, s, 0)
        y += jnp.where(d == 2, s, 0)
        z += jnp.where(d == 3, s, 0)
        m, s = m // 4, s * 2
    return [x, y, z]


@register_map("sierpinski3d", "bitwise", tier="membership")
def sierpinski3d_membership(axes, ndigits):
    del ndigits
    x, y, z = axes
    return ((x & y) | (x & z) | (y & z)) == 0


@register_map("menger3d", "bitwise", tier="pallas")
def menger3d_coords(lam, ndigits):
    x = jnp.zeros_like(lam)
    y = jnp.zeros_like(lam)
    z = jnp.zeros_like(lam)
    m, s = lam, 1
    for _ in range(ndigits):
        cell = m % 20
        for void in _MENGER_VOID_CELLS:   # ascending skip ladder
            cell += (cell >= void).astype(jnp.int32)
        x += (cell // 9) * s
        y += ((cell // 3) % 3) * s
        z += (cell % 3) * s
        m, s = m // 20, s * 3
    return [x, y, z]


@register_map("menger3d", "bitwise", tier="membership")
def menger3d_membership(axes, ndigits):
    x, y, z = axes
    ok = jnp.ones(x.shape, dtype=bool)
    for _ in range(ndigits):
        ones = ((x % 3 == 1).astype(jnp.int32) + (y % 3 == 1) + (z % 3 == 1))
        ok &= ones < 2
        x, y, z = x // 3, y // 3, z // 3
    return ok


# ---------------------------------------------------------------------------
# m-simplex family: vectorized m-th-root layer peel (generalizes _vec_isqrt /
# _tet_z — fp32 seed + exact int32 correction ladder, one peel per level).
# The peel itself is the module-generic implementation in core/msimplex.py
# (shared with the numpy/jnp tiers), instantiated here with jax.numpy.
# ---------------------------------------------------------------------------


def _register_msimplex_tiers(m: int):
    def coords(lam, ndigits, _m=m):
        del ndigits  # closed-form per level; digits are a fractal concept
        rem = lam
        axes = []
        for level in range(_m, 0, -1):
            x = ms.vec_simplex_layer(jnp, rem, level)
            axes.append(x)
            rem = rem - ms.vec_simplex_size(jnp, x, level)
        return list(reversed(axes))

    def membership(axes, ndigits):
        del ndigits
        ok = axes[0] >= 0
        for lo, hi in zip(axes, axes[1:]):
            ok &= lo <= hi
        return ok

    register_map(f"msimplex{m}", "analytical",
                 tiers={"pallas": coords, "membership": membership})


for _m in MSIMPLEX_MS:
    _register_msimplex_tiers(_m)


# ---------------------------------------------------------------------------
# Embedded-2D-fractal family: generic digit engine driven by the domain's
# generator table (arithmetic where-ladders — no gathers), so a new family
# member needs no kernel code at all.
# ---------------------------------------------------------------------------


def _register_embedded_fractal_tiers(domain):
    base, scale, dim = domain.base, domain.scale, domain.dim
    vecs = tuple(tuple(int(x) for x in v) for v in domain.vecs)
    cell_codes = [sum(v[k] * scale ** (dim - 1 - k) for k in range(dim))
                  for v in vecs]

    def coords(lam, ndigits):
        axes = [jnp.zeros_like(lam) for _ in range(dim)]
        m, s = lam, 1
        for _ in range(ndigits):
            d = m % base
            for k in range(dim):
                for digit, v in enumerate(vecs):
                    if v[k]:
                        axes[k] += jnp.where(d == digit, v[k] * s, 0)
            m, s = m // base, s * scale
        return axes

    def membership(axes, ndigits):
        ok = jnp.ones(axes[0].shape, dtype=bool)
        cur = list(axes)
        for _ in range(ndigits):
            code = jnp.zeros_like(cur[0])
            for k in range(dim):
                code = code * scale + cur[k] % scale
            hit = jnp.zeros_like(ok)
            for c in cell_codes:
                hit |= code == c
            ok &= hit
            cur = [a // scale for a in cur]
        return ok

    register_map(domain.name, "bitwise",
                 tiers={"pallas": coords, "membership": membership})


for _dom in EMBEDDED_FRACTAL_DOMAINS:
    _register_embedded_fractal_tiers(_dom)
