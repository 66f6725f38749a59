"""Fused TD-target + Huber loss — Pallas TPU kernel.

The elementwise tail of the DQN update, fused into a single VMEM pass:

    target = r + gamma * (1 - done) * max_a Q'(s', a)    [target net]
    delta  = Q(s, a_sel) - stop_grad(target)
    loss   = 0.5 delta^2            if |delta| <= 1
             |delta| - 0.5          otherwise
    dq     = dloss/dQ(s, a_sel) = clip(delta, -1, 1)

Returns (loss, dq) per sample; the caller wires dq into the Q-network
backward pass (custom_vjp in ops.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

F32 = jnp.float32


def _kernel(qsel_ref, qnext_ref, r_ref, done_ref, loss_ref, dq_ref, *, gamma: float):
    qnext = qnext_ref[...]  # [bb, A]
    best = jnp.max(qnext, axis=-1, keepdims=True)  # [bb, 1]
    r = r_ref[...]
    done = done_ref[...]
    target = r + gamma * (1.0 - done) * best
    delta = qsel_ref[...] - target
    absd = jnp.abs(delta)
    loss_ref[...] = jnp.where(absd <= 1.0, 0.5 * delta * delta, absd - 0.5)
    dq_ref[...] = jnp.clip(delta, -1.0, 1.0)


def fused_td(
    q_sel,
    q_next,
    reward,
    done,
    *,
    gamma: float,
    block_b: int = 128,
    interpret: bool,
):
    """q_sel [B,1], q_next [B,A], reward [B,1], done [B,1] ->
    (loss [B,1], dq [B,1])."""
    b, a = q_next.shape
    block_b = min(block_b, b)
    nb = b // block_b
    assert nb * block_b == b, (b, block_b)
    kern = functools.partial(_kernel, gamma=gamma)
    return pl.pallas_call(
        kern,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_b, a), lambda i: (i, 0)),
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, 1), F32),
            jax.ShapeDtypeStruct((b, 1), F32),
        ],
        interpret=interpret,
        name="fused_td",
    )(q_sel.astype(F32), q_next.astype(F32), reward.astype(F32), done.astype(F32))
