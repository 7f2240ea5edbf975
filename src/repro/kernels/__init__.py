"""Pallas TPU kernels for the eigensolver hot spots the paper optimizes.

  secular_roots.py    -- batched secular root solve (CUDA block-reduction
                         analogue; grid over root blocks, pole-tile loop)
  fused_update.py     -- fused conquer post-pass: one delta sweep emits the
                         Gu-Eisenstat weights AND the selected-row update
  sturm_count.py      -- batched Sturm-sequence eigenvalue counts for the
                         spectrum-slicing front end (grid over problems x
                         probe-shift blocks)

ops.py dispatches between the Pallas kernels (TPU / interpret), the
chunked XLA fallbacks, and the dense small-K path (size-adaptive level
dispatch), by device backend and operand dtype; layout.py holds the
row/column helpers the kernels share; ref.py holds deliberately-naive
dense oracles.
"""

from repro.kernels.ops import (
    resolve_backend,
    secular_postpass,
    secular_postpass_batched,
    secular_solve,
    secular_solve_batched,
    set_backend,
    sturm_count_batched,
)
from repro.kernels.sturm_count import sturm_count_pallas_batch
from repro.kernels.secular_roots import (secular_solve_pallas,
                                         secular_solve_pallas_batch)
from repro.kernels.fused_update import (secular_postpass_pallas,
                                        secular_postpass_pallas_batch)

__all__ = [
    "resolve_backend",
    "secular_postpass", "secular_postpass_batched", "secular_postpass_pallas",
    "secular_postpass_pallas_batch",
    "secular_solve", "secular_solve_batched", "secular_solve_pallas",
    "secular_solve_pallas_batch", "set_backend",
    "sturm_count_batched", "sturm_count_pallas_batch",
]
