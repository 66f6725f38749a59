"""jit'd wrapper: accepts [..., d] and flattens leading dims."""

from __future__ import annotations

from functools import partial

import jax

from repro.kernels.rmsnorm.kernel import rmsnorm as _kernel


@partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm(
    x, scale, *, eps: float = 1e-5, block_rows: int = 128, interpret: bool = False
):
    lead = x.shape[:-1]
    y = _kernel(
        x.reshape(-1, x.shape[-1]),
        scale,
        eps=eps,
        block_rows=block_rows,
        interpret=interpret,
    )
    return y.reshape(*lead, x.shape[-1])
