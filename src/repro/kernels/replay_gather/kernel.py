"""ERB batched gather — Pallas TPU kernel.

The ADFLL sampling hot path: gather a minibatch of experience rows from an
HBM-resident replay buffer by precomputed indices, scaling each row by its
(renormalized) importance weight. On TPU this is bandwidth-bound; the
idiomatic formulation is a ``PrefetchScalarGridSpec`` — the index vector is
scalar-prefetched so the BlockSpec index_map can route each grid step's HBM
-> VMEM copy straight to the requested buffer row (no gather op in the
kernel body at all; the DMA engine does the work).

Layout. The buffer is a row view ``[cap, 1, feat]``: the row index is a
leading dimension, which a block may select one at a time. A 2-D
``[cap, feat]`` buffer cannot be gathered this way — its rows sit on the
tiled second-minor dimension, and the TPU compiler refuses a one-row
block or DMA slice there (the tile is 8 rows). With ``feat`` a multiple of
128 the TPU's default layout of ``[cap, 1, feat]`` keeps every row
contiguous, so the kernel reads the caller's buffer in place; with any
other width the default layout is column-major and XLA copies the whole
buffer before the kernel (see ``ops.pool_width``).

Grid: one step per gathered row; each step copies one buffer row into
VMEM, applies the weight, and writes the output row.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32


def _kernel(idx_ref, w_ref, buf_ref, out_ref):
    # buf_ref block: [1, 1, feat] — the row selected by the index_map.
    i = pl.program_id(0)
    out_ref[...] = buf_ref[...] * w_ref[i]


def replay_gather(buffer, indices, weights, *, interpret: bool):
    """buffer [cap, 1, feat], indices [batch] int32, weights [batch] f32
    -> [batch, 1, feat] (buffer rows scaled by weights)."""
    cap, one, feat = buffer.shape
    assert one == 1, buffer.shape
    batch = indices.shape[0]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # indices, weights
        grid=(batch,),
        in_specs=[
            pl.BlockSpec((1, 1, feat), lambda i, idx_ref, w_ref: (idx_ref[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, feat), lambda i, idx_ref, w_ref: (i, 0, 0)),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((batch, 1, feat), buffer.dtype),
        interpret=interpret,
        name="replay_gather",
    )(indices, weights.astype(buffer.dtype), buffer)
