"""Vector-layout helpers shared by the Pallas kernels.

Mosaic (the TPU kernel compiler) works on 2-D tiles of (8 sublanes x 128
lanes) and refuses 1-D iotas, 1-D gathers and value-level dynamic slices.
The kernels therefore keep every per-problem vector as a ``(1, K)`` row in
a ``(B, 1, K)`` array, read with ``(1, 1, K)`` blocks, and put the two
sides of each pairwise sweep on the two vector axes: the quantity being
solved for on the sublanes as a ``(C, 1)`` column, the quantity being
reduced over on the lanes as a ``(1, T)`` row.

Moving a vector between the two axes, and the few gathers the secular
iteration needs, are one-hot selects followed by a lane or sublane sum.
A sum of one value and zeros is exact, so these are bit-identical to the
transposes and gathers they replace, in any dtype.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

# Index-map zero: under x64 a Python 0 returned by an index map is traced
# as int64, which Mosaic refuses.
ZERO = np.int32(0)


def per_problem(shape):
    """BlockSpec of problem b's whole (1, rows, K) slab, on a grid whose
    axis 0 is the problem."""
    return pl.BlockSpec(shape, lambda b, *_: (b, ZERO, ZERO))


def per_block(shape):
    """BlockSpec of block i of problem b's last axis, on a (B, blocks)
    grid."""
    return pl.BlockSpec(shape, lambda b, i: (b, ZERO, i))


def where(cond, x, y):
    """``jnp.where`` whose Python-scalar branch takes the other branch's
    dtype.  Under x64, ``jnp.where`` passes a Python scalar in as a 64-bit
    value, and Mosaic has no 64-bit conversions."""
    if isinstance(x, (int, float)):
        x = jnp.asarray(x, y.dtype)
    elif isinstance(y, (int, float)):
        y = jnp.asarray(y, x.dtype)
    return jnp.where(cond, x, y)


def col_iota(n: int):
    """(n, 1) int32 indices on the sublanes."""
    return jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)


def row_iota(n: int):
    """(1, n) int32 indices on the lanes."""
    return jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)


def gather_col(row, idx):
    """``row[0, idx]`` as a column: row (1, K), idx (C, 1) int -> (C, 1)."""
    hit = row_iota(row.shape[-1]) == idx
    return jnp.sum(where(hit, row, 0), axis=1, keepdims=True)


def row_to_col(row):
    """Transpose a (1, C) row into a (C, 1) column."""
    return gather_col(row, col_iota(row.shape[-1]))


def col_to_row(col):
    """Transpose a (C, 1) column into a (1, C) row."""
    n = col.shape[0]
    hit = col_iota(n) == row_iota(n)
    return jnp.sum(where(hit, col, 0), axis=0, keepdims=True)


def round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def fori(n: int, body, init):
    """``fori_loop`` over [0, n) with an int32 induction variable: under
    x64, Python-int bounds give an int64 one, which Mosaic refuses."""
    return jax.lax.fori_loop(jnp.int32(0), jnp.int32(n), body, init)
