"""Per-kernel correctness: Pallas (interpret=True) vs pure-jnp oracles,
swept over shapes / dtypes / masking configs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.fused_td.kernel import fused_td
from repro.kernels.fused_td.ops import td_loss
from repro.kernels.fused_td.ref import fused_td_ref
from repro.kernels.replay_gather.ops import replay_gather
from repro.kernels.replay_gather.ref import replay_gather_ref


def _qkv(rng, b, s, hq, hkv, d, dtype):
    q = jnp.asarray(rng.standard_normal((b, s, hq, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, s, hkv, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, s, hkv, d)), dtype)
    return q, k, v


@pytest.mark.parametrize(
    "b,s,hq,hkv,d",
    [
        (1, 128, 4, 4, 32),  # MHA
        (2, 256, 8, 2, 16),  # GQA 4:1
        (1, 512, 4, 1, 64),  # MQA
    ],
)
@pytest.mark.parametrize("window", [None, 64])
def test_flash_attention_matches_ref(rng, b, s, hq, hkv, d, window):
    q, k, v = _qkv(rng, b, s, hq, hkv, d, jnp.float32)
    out = flash_attention(
        q, k, v, causal=True, window=window, block_q=64, block_k=64, interpret=True
    )
    ref = attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 2e-5), (jnp.bfloat16, 3e-2)])
def test_flash_attention_dtypes(rng, dtype, atol):
    q, k, v = _qkv(rng, 1, 128, 4, 2, 32, dtype)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64, interpret=True)
    ref = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=atol, rtol=atol
    )


def test_flash_attention_softcap(rng):
    q, k, v = _qkv(rng, 1, 128, 2, 2, 16, jnp.float32)
    out = flash_attention(
        q, k, v, causal=True, softcap=20.0, block_q=64, block_k=64, interpret=True
    )
    ref = attention_ref(q, k, v, causal=True, softcap=20.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_attention_uneven_blocks(rng):
    # s not a multiple of the block sizes exercises the tail masking
    q, k, v = _qkv(rng, 1, 96, 2, 2, 16, jnp.float32)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64, interpret=True)
    ref = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("cap,feat,batch", [(64, 16, 8), (256, 128, 32), (128, 33, 5)])
def test_replay_gather_matches_ref(rng, cap, feat, batch):
    buf = jnp.asarray(rng.standard_normal((cap, 1, feat)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, cap, batch), jnp.int32)
    w = jnp.asarray(rng.random(batch), jnp.float32)
    out = replay_gather(buf, idx, w, mode="interpret")
    ref = replay_gather_ref(buf, idx, w)
    assert out.shape == (batch, 1, feat)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_replay_gather_modes():
    from repro.kernels.replay_gather.ops import pool_width, resolve_mode

    assert resolve_mode() == resolve_mode("auto") == "ref"  # CPU: XLA gather
    assert resolve_mode("interpret") == "interpret"
    with pytest.raises(ValueError):
        resolve_mode("fast")
    assert [pool_width(f) for f in (1, 128, 1033, 182259)] == [128, 128, 1152, 182272]


@pytest.mark.parametrize("b,a,gamma", [(64, 6, 0.9), (128, 4, 0.99), (32, 6, 0.5)])
def test_fused_td_matches_ref(rng, b, a, gamma):
    q_sel = jnp.asarray(rng.standard_normal((b, 1)), jnp.float32)
    q_next = jnp.asarray(rng.standard_normal((b, a)), jnp.float32)
    r = jnp.asarray(rng.standard_normal((b, 1)), jnp.float32)
    d = jnp.asarray((rng.random((b, 1)) < 0.3), jnp.float32)
    l1, dq1 = fused_td(q_sel, q_next, r, d, gamma=gamma, block_b=32, interpret=True)
    l2, dq2 = fused_td_ref(q_sel, q_next, r, d, gamma=gamma)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), atol=1e-6)
    np.testing.assert_allclose(np.asarray(dq1), np.asarray(dq2), atol=1e-6)


def test_td_loss_gradient_matches_autodiff(rng):
    """custom_vjp (fused dq) must equal autodiff through the ref loss."""
    b = 64
    q_sel = jnp.asarray(rng.standard_normal((b, 1)), jnp.float32)
    q_next = jnp.asarray(rng.standard_normal((b, 6)), jnp.float32)
    r = jnp.asarray(rng.standard_normal((b, 1)), jnp.float32)
    d = jnp.zeros((b, 1), jnp.float32)

    g_fused = jax.grad(lambda q: td_loss(q, q_next, r, d, 0.9, True, True))(q_sel)

    def ref_loss(q):
        loss, _ = fused_td_ref(q, q_next, r, d, gamma=0.9)
        return jnp.mean(loss)

    g_ref = jax.grad(ref_loss)(q_sel)
    np.testing.assert_allclose(np.asarray(g_fused), np.asarray(g_ref), atol=1e-6)
