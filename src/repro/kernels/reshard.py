"""Pallas TPU kernel: row gather for the elastic reshard executor.

The N-to-M repartition (elastic/plan.py) reduces to moving row ranges of each
leaf into the new shards. On device that is a gather: the recovered source
rows sit stacked in HBM as one (rows, cols) matrix, and each new-shard row i
is ``src[idx[i]]``. The row indices are known before the kernel runs, so they
ride in as scalar prefetch — the BlockSpec index map reads ``idx_ref`` and the
DMA engine streams exactly the rows the plan selected, once, with no
intermediate host copy.

Layout: rows are lane-padded to LANE_COLS multiples and viewed as
(rows, 1, cols), so one row is a (1, LANE_COLS) block of its own trailing
(1, cols) plane — which satisfies the TPU tiling rule. The grid walks
(out_row, col_block) and the source row of each block comes from the
prefetched index vector.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE_COLS = 128  # native lane width; ops.gather_rows pads columns to this


def _gather_kernel(idx_ref, x_ref, o_ref):
    del idx_ref  # consumed by the index maps
    o_ref[...] = x_ref[...]


def gather_rows_pallas(src: jax.Array, idx: jax.Array, *, interpret: bool) -> jax.Array:
    """src: (rows, cols) with cols % LANE_COLS == 0; idx: (rows_out,) int32.

    Returns (rows_out, cols) where out[i] = src[idx[i]]. Wrapper-level column
    padding and dtype viewing live in ops.gather_rows.
    """
    rows_out = idx.shape[0]
    rows, cols = src.shape
    assert cols % LANE_COLS == 0, cols
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(rows_out, cols // LANE_COLS),
        in_specs=[
            pl.BlockSpec(
                (None, 1, LANE_COLS), lambda i, j, idx_ref: (idx_ref[i], 0, j)
            ),
        ],
        out_specs=pl.BlockSpec((None, 1, LANE_COLS), lambda i, j, idx_ref: (i, 0, j)),
    )
    out = pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows_out, 1, cols), src.dtype),
        interpret=interpret,
    )(idx.astype(jnp.int32), src.reshape(rows, 1, cols))
    return out.reshape(rows_out, cols)
