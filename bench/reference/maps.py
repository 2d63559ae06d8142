"""Exact λ → coordinate maps in plain numpy, from the domain definitions in
the configuration file (the papers' constructions), sharing no code with
the program.

Families:

* ``triangle``  — {(x, y) : 0 <= y <= x}, rows in order: λ's row x is the
  largest with x(x+1)/2 <= λ, y the rank inside the row; answer (x, y).
* ``pyramid``   — {(x, y, z) : 0 <= y <= x <= z}, layers in order: z is
  the largest with z(z+1)(z+2)/6 <= λ, the rest a triangle; answer (x, y, z).
* ``simplex``   — {(x_1 <= ... <= x_m)}: x_m is the largest with
  C(x_m + m - 1, m) <= λ, peeled down to x_1; answer (x_1, ..., x_m).
* ``digits``    — self-similar fractals: λ = Σ d_i B^i gives
  Σ cells[d_i] · scale^i.

The outer coordinate comes from a table of domain sizes searched with
``np.searchsorted`` — not the program's float seed and correction ladder.
"""
from __future__ import annotations

import math

import numpy as np


def _sizes(size_of, top: int) -> np.ndarray:
    """size_of(0), size_of(1), ... up to the first entry above ``top``."""
    out = []
    x = 0
    while True:
        s = size_of(x)
        out.append(s)
        if s > top:
            return np.asarray(out, dtype=np.int64)
        x += 1


def _outer(lams: np.ndarray, size_of) -> tuple[np.ndarray, np.ndarray]:
    """(x, λ − size_of(x)) with x the largest such that size_of(x) <= λ."""
    if lams.size == 0:
        return lams.copy(), lams.copy()
    table = _sizes(size_of, int(lams.max()))
    x = np.searchsorted(table, lams, side="right") - 1
    return x, lams - table[x]


def _simplex(lams: np.ndarray, m: int) -> list[np.ndarray]:
    """(x_1, ..., x_m) for the m-simplex."""
    cols = []
    rem = lams
    for level in range(m, 1, -1):
        x, rem = _outer(rem, lambda s, k=level: math.comb(s + k - 1, k))
        cols.append(x)
    cols.append(rem)
    return cols[::-1]


def _digits(lams: np.ndarray, base: int, scale: int,
            cells: list[list[int]]) -> list[np.ndarray]:
    table = np.asarray(cells, dtype=np.int64)
    out = np.zeros((len(lams), table.shape[1]), dtype=np.int64)
    rem = lams.copy()
    weight = 1
    while (rem > 0).any():
        out += table[rem % base] * weight
        rem //= base
        weight *= scale
    return [out[:, k] for k in range(table.shape[1])]


def coords(spec: dict, start: int, n_points: int) -> np.ndarray:
    """Exact (n_points, dim) int64 coordinates of λ in [start, start + n)."""
    lams = np.arange(start, start + n_points, dtype=np.int64)
    family = spec["family"]
    if family == "triangle":
        x, y = _outer(lams, lambda s: s * (s + 1) // 2)
        cols = [x, y]
    elif family == "pyramid":
        z, rem = _outer(lams, lambda s: s * (s + 1) * (s + 2) // 6)
        x, y = _outer(rem, lambda s: s * (s + 1) // 2)
        cols = [x, y, z]
    elif family == "simplex":
        cols = _simplex(lams, spec["m"])
    elif family == "digits":
        cols = _digits(lams, spec["base"], spec["scale"], spec["cells"])
    else:
        raise ValueError(f"unknown domain family {family!r}")
    return np.stack(cols, axis=-1)


def dim(spec: dict) -> int:
    family = spec["family"]
    if family == "triangle":
        return 2
    if family == "pyramid":
        return 3
    if family == "simplex":
        return spec["m"]
    return len(spec["cells"][0])


def narrowed(ref: np.ndarray) -> np.ndarray:
    """The control: the reference answer sent as int16, the narrower wire
    type a later change might pick to halve the bytes.  It breaks the
    configuration's int32 guarantee wherever a coordinate passes 32767."""
    return ref.astype(np.int16).astype(np.int64)


def mismatches(got, want: np.ndarray) -> int:
    """Coordinates that differ from the reference.  An answer of the wrong
    shape or of a type other than int32 counts every coordinate."""
    got = np.asarray(got)
    if got.shape != want.shape or got.dtype != np.int32:
        return int(want.size)
    return int(np.count_nonzero(got.astype(np.int64) != want))
