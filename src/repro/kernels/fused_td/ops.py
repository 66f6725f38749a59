"""jit'd wrapper with custom_vjp so the fused dq drives the DQN backward.

``use_pallas`` selects the fused kernel over the XLA oracle; the kernel
runs compiled unless the caller passes ``interpret=True`` (the CPU tests
do — a compiled Pallas kernel exists only on TPU)."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.fused_td.kernel import fused_td as _kernel
from repro.kernels.fused_td.ref import fused_td_ref


def _loss_dq(q_sel, q_next, reward, done, gamma, use_pallas, interpret):
    if use_pallas:
        return _kernel(q_sel, q_next, reward, done, gamma=gamma, interpret=interpret)
    return fused_td_ref(q_sel, q_next, reward, done, gamma=gamma)


@partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def td_loss(
    q_sel,
    q_next,
    reward,
    done,
    gamma: float = 0.99,
    use_pallas: bool = True,
    interpret: bool = False,
):
    """Mean Huber TD loss. Differentiable in q_sel (target is stopped)."""
    loss, _ = _loss_dq(q_sel, q_next, reward, done, gamma, use_pallas, interpret)
    return jnp.mean(loss)


def _fwd(q_sel, q_next, reward, done, gamma, use_pallas, interpret):
    loss, dq = _loss_dq(q_sel, q_next, reward, done, gamma, use_pallas, interpret)
    return jnp.mean(loss), (dq, q_sel.shape[0])


def _bwd(gamma, use_pallas, interpret, res, g):
    dq, b = res
    return (g * dq / b, None, None, None)


td_loss.defvjp(_fwd, _bwd)
