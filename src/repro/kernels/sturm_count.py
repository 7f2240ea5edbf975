"""Pallas TPU kernel: batched Sturm-sequence eigenvalue counts.

The spectrum-slicing front end (core/bisect.py) spends its entire budget
evaluating #{eigenvalues <= shift} at batches of probe shifts -- one
sequential pivot recurrence per shift, embarrassingly parallel across
shifts and across problems.  Mapping:

  work axis                     TPU / Pallas
  ----------------------------  ------------------------------------------
  problems (B)                  grid axis 0
  probe shifts (S)              grid axis 1 in blocks of SHIFT_BLOCK shifts,
                                laid out (rows, 128 lanes) so a block of
                                1024 shifts is one (8, 128) vector; every
                                element carries its own shift's pivot
  matrix rows (n)               sequential fori; d and e^2 stay in HBM and
                                are copied ROW_CHUNK rows at a time into
                                SMEM, where each row is a scalar read
                                (the recurrence is a linear chain -- this
                                is the irreducible dependence)

The recurrence runs over the shifted squared off-diagonals
``e2s = [0, e^2]`` from a unit starting pivot, so row 0 is the same
update as every other row.  The count uses LAPACK DSTEBZ's guarded
negcount convention (pivots within ``pivmin`` of zero are counted as
negative), identical to the XLA scan in ``core.bisect.sturm_count_xla``
-- ref.py / tests assert exact integer agreement across shapes, dtypes
and degenerate (zero off-diagonal) inputs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.layout import ZERO, fori, per_problem, round_up, where

DEFAULT_SHIFT_BLOCK = 1024
ROW_CHUNK = 1024
_LANES = 128


def _sturm_kernel(d_hbm, e2_hbm, shifts_ref, pivmin_ref, count_ref,
                  d_buf, e2_buf, d_sem, e2_sem, *, n, row_stride):
    # d_hbm, e2_hbm: flat (B * row_stride,) in HBM; shifts (1, R, L);
    # pivmin (1, 1, 1); d_buf, e2_buf: (chunk,) SMEM; grid = (B, blocks).
    chunk = d_buf.shape[0]
    base = pl.program_id(0) * row_stride
    sig = shifts_ref[0]
    pivmin = pivmin_ref[0]

    def rows(start, count, carry):
        src = pl.ds(pl.multiple_of(base + start, chunk), chunk)
        copies = [pltpu.make_async_copy(hbm.at[src], buf, sem)
                  for hbm, buf, sem in ((d_hbm, d_buf, d_sem),
                                        (e2_hbm, e2_buf, e2_sem))]
        for cp in copies:
            cp.start()
        for cp in copies:
            cp.wait()

        def row(j, carry):
            q, cnt = carry
            q = (d_buf[j] - sig) - e2_buf[j] / q
            q = where(jnp.abs(q) < pivmin, -pivmin, q)
            return q, cnt + (q <= 0.0).astype(jnp.int32)
        return fori(count, row, carry)

    carry = (jnp.ones(sig.shape, sig.dtype), jnp.zeros(sig.shape, jnp.int32))
    full, tail = divmod(n, chunk)
    carry = fori(
        full, lambda c, carry: rows(c * chunk, chunk, carry), carry)
    if tail:
        carry = rows(full * chunk, tail, carry)
    count_ref[0] = carry[1]


@functools.partial(jax.jit, static_argnames=("shift_block", "interpret"))
def sturm_count_pallas_batch(d, e2, shifts, pivmin, *,
                             shift_block: int = DEFAULT_SHIFT_BLOCK,
                             interpret: bool = False):
    """Batched Pallas Sturm counts: grid over problems x shift blocks.

    d: (B, n); e2: (B, n-1) squared off-diagonals; shifts: (B, S);
    pivmin: (B, 1) pivot floors.  One kernel launch counts every
    (problem, shift) pair -- the whole bisection front's per-iteration
    work.  Returns (B, S) int32 counts (eigenvalues <= shift).
    """
    B, n = d.shape
    S = shifts.shape[1]
    L = min(shift_block, _LANES)
    if shift_block % L:
        raise ValueError(f"shift_block must be <= {_LANES} or a multiple "
                         f"of it, got {shift_block}")
    rows_total = -(-S // L)
    R = min(shift_block // L, rows_total)
    nblk = -(-rows_total // R)
    Sp = nblk * R * L
    if Sp != S:
        # Pad lanes with the last shift: duplicated counts, sliced away.
        shifts = jnp.concatenate(
            [shifts, jnp.broadcast_to(shifts[:, -1:], (B, Sp - S))], axis=1)

    # Whole ROW_CHUNKs per problem: Mosaic tiles a flat HBM array in
    # 1024-element units, so every copy must start on one.
    chunk = ROW_CHUNK
    stride = round_up(n, chunk)
    e2s = jnp.zeros((B, stride), d.dtype).at[:, 1:n].set(e2)
    d_flat = jnp.pad(d, ((0, 0), (0, stride - n))).reshape(-1)
    pivmin = jnp.asarray(pivmin, d.dtype).reshape(B, 1, 1)

    block = pl.BlockSpec((1, R, L), lambda b, i: (b, i, ZERO))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    counts = pl.pallas_call(
        functools.partial(_sturm_kernel, n=n, row_stride=stride),
        grid=(B, nblk),
        in_specs=[hbm, hbm, block,
                  per_problem((1, 1, 1))],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct((B, nblk * R, L), jnp.int32),
        scratch_shapes=[pltpu.SMEM((chunk,), d.dtype),
                        pltpu.SMEM((chunk,), d.dtype),
                        pltpu.SemaphoreType.DMA(()),
                        pltpu.SemaphoreType.DMA(())],
        interpret=interpret,
    )(d_flat, e2s.reshape(-1), shifts.reshape(B, nblk * R, L), pivmin)
    return counts.reshape(B, Sp)[:, :S]
