"""jit'd wrapper for the ERB gather kernel.

``mode`` selects the lowering:

* ``"auto"`` (default) — the backend's named choice, :data:`TPU_MODE` on
  TPU and ``"ref"`` everywhere else. :func:`resolve_mode` says which one a
  process gets; callers that report what ran print that name.
* ``"compiled"`` — the Pallas kernel compiled for the backend (TPU only).
* ``"interpret"`` — the Pallas kernel under the Pallas interpreter
  (kernel-correctness tests on CPU and debugging; a per-grid-step
  simulator, orders of magnitude slower than XLA's gather — never a hot
  path, and never chosen by ``"auto"``).
* ``"ref"`` — the pure-XLA oracle (`replay_gather_ref`), bit-identical
  output.

Buffers are ``[cap, 1, F]`` row views (see ``kernel.py``); hot paths keep
their pools as ``[R, 1, pool_width(F)]`` so that on TPU the kernel reads
them in place.
"""

from __future__ import annotations

from functools import partial

import jax

from repro.kernels.replay_gather.kernel import replay_gather as _kernel
from repro.kernels.replay_gather.ref import replay_gather_ref

MODES = ("auto", "compiled", "interpret", "ref")

# The TPU choice of ``"auto"``: the compiled kernel reads a [R, 1, F] pool
# in place (no temporary at 8^3 or 45^3 widths), where XLA's gather of the
# equivalent [R, F] pool at 45^3 makes a pool-sized temporary.
TPU_MODE = "compiled"

LANES = 128  # TPU vector lane count: the minor tile of every f32 layout


def pool_width(feat: int) -> int:
    """Row width of a device pool holding ``feat``-wide rows: ``feat``
    rounded up to a whole number of lanes. At that width the TPU's
    default layout of ``[R, 1, width]`` stores each row contiguously,
    which is what lets the kernel read the pool without a copy."""
    return -(-feat // LANES) * LANES


def resolve_mode(mode: str = "auto") -> str:
    """The concrete implementation ``mode`` selects in this process."""
    if mode not in MODES:
        raise ValueError(f"unknown replay_gather mode: {mode!r}")
    if mode == "auto":
        return TPU_MODE if jax.default_backend() == "tpu" else "ref"
    return mode


@partial(jax.jit, static_argnames=("mode",))
def replay_gather(buffer, indices, weights, *, mode: str = "auto"):
    """Gather + weight replay rows: buffer [cap, 1, F], indices [B],
    weights [B] -> [B, 1, F]."""
    mode = resolve_mode(mode)
    if mode == "ref":
        return replay_gather_ref(buffer, indices, weights)
    return _kernel(buffer, indices, weights, interpret=mode == "interpret")
