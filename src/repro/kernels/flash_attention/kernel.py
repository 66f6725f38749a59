"""FlashAttention-2 style fused attention — Pallas TPU kernel.

Grid: (batch*q_heads, n_q_blocks, n_kv_blocks); the kv dimension is the
innermost (sequential) grid axis, so the online-softmax state lives in VMEM
scratch across kv steps. GQA is expressed in the k/v BlockSpec index maps
(query head h reads kv head h // group_size) — no kv replication in HBM.

Sliding-window and causal masking are applied with block-level iota; fully
masked blocks short-circuit via ``pl.when`` (on real TPU the MXU work is
skipped; under interpret=True it is merely branch-masked).

VMEM budget per step: q/k/v blocks (block_q + 2 block_k) x head_dim plus
(block_q x head_dim) f32 accumulator — callers pick block sizes so this
stays within ~16 MB (ops.py defaults: 256/512 x 128).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
NEG_INF = -1e30


def _kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,  # refs
    m_scr,
    l_scr,
    acc_scr,  # scratch
    *,
    scale: float,
    causal: bool,
    window,
    softcap: float,
    block_q: int,
    block_k: int,
    n_kv: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k
    # block is fully masked iff every k position is after every q position
    # (causal) or before the window of every q position.
    run = True
    if causal:
        run = k_start <= q_start + block_q - 1
    if window is not None:
        run = jnp.logical_and(run, q_start - (k_start + block_k - 1) < window)

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(F32)  # [bq, d]
        k = k_ref[0].astype(F32)  # [bk, d]
        v = v_ref[0].astype(F32)  # [bk, dv]
        s = (
            jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), preferred_element_type=F32
            )
            * scale
        )
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= (qpos - kpos) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_prev * corr + p.sum(-1, keepdims=True)
        m_scr[...] = m_new
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=F32
        )

    @pl.when(ki == n_kv - 1)
    def _finalize():
        l_sum = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l_sum).astype(o_ref.dtype)


def flash_attention_bhsd(
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
    """q [BH, S, D], k/v [BH_kv, S, D*] (BH = BH_kv * group). -> [BH, S, Dv]."""
    bh, s, d = q.shape
    bh_kv = k.shape[0]
    dv = v.shape[-1]
    g = bh // bh_kv
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    nq = math.ceil(s / block_q)
    nk = math.ceil(s / block_k)
    if scale is None:
        scale = d**-0.5

    kern = functools.partial(
        _kernel,
        scale=scale,
        causal=causal,
        window=window,
        softcap=softcap,
        block_q=block_q,
        block_k=block_k,
        n_kv=nk,
    )
    return pl.pallas_call(
        kern,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j, g=g: (b // g, j, 0)),
            pl.BlockSpec((1, block_k, dv), lambda b, i, j, g=g: (b // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, dv), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), F32),
            pltpu.VMEM((block_q, 1), F32),
            pltpu.VMEM((block_q, dv), F32),
        ],
        interpret=interpret,
    )(q, k, v)
