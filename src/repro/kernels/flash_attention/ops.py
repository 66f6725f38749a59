"""jit'd public wrapper: [B,S,H,D] layout -> kernel layout and back."""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_bhsd


@partial(
    jax.jit,
    static_argnames=(
        "causal",
        "window",
        "scale",
        "softcap",
        "block_q",
        "block_k",
        "interpret",
    ),
)
def flash_attention(
    q,
    k,
    v,
    *,
    causal: bool = True,
    window=None,
    scale=None,
    softcap: float = 0.0,
    block_q: int = 256,
    block_k: int = 512,
    interpret: bool = False,
):
    """q [B,S,Hq,D], k/v [B,S,Hkv,D*] -> [B,S,Hq,Dv]."""
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    dv = v.shape[-1]

    # pad S to a block multiple (padded kv rows are causally masked; padded
    # q rows are sliced away) — keeps the kernel free of tail masking.
    pad = (-s) % math.lcm(min(block_q, s), min(block_k, s))
    if pad:
        padw = ((0, 0), (0, pad), (0, 0), (0, 0))
        q = jnp.pad(q, padw)
        k = jnp.pad(k, padw)
        v = jnp.pad(v, padw)
    sp = s + pad

    def to_bhsd(x):
        return jnp.moveaxis(x, 2, 1).reshape(b * x.shape[2], sp, x.shape[-1])

    # interleave kv heads so q head h maps to kv head h // g within a batch
    out = flash_attention_bhsd(
        to_bhsd(q),
        to_bhsd(k),
        to_bhsd(v),
        causal=causal,
        window=window,
        scale=scale,
        softcap=softcap,
        block_q=block_q,
        block_k=block_k,
        interpret=interpret,
    )
    return jnp.moveaxis(out.reshape(b, hq, sp, dv), 1, 2)[:, :s]
