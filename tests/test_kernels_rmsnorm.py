"""rmsnorm kernel: shape/dtype sweep vs oracle + hypothesis property."""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels.rmsnorm.ops import rmsnorm
from repro.kernels.rmsnorm.ref import rmsnorm_ref


@pytest.mark.parametrize("shape", [(8, 64), (2, 16, 128), (5, 96), (1, 256)])
@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 1e-6), (jnp.bfloat16, 2e-2)])
def test_rmsnorm_matches_ref(rng, shape, dtype, atol):
    x = jnp.asarray(rng.standard_normal(shape), dtype)
    scale = jnp.asarray(rng.standard_normal(shape[-1]), jnp.float32)
    out = rmsnorm(x, scale, block_rows=4, interpret=True)
    ref = rmsnorm_ref(x, scale)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=atol, rtol=atol
    )


@settings(max_examples=15, deadline=None)
@given(n=st.integers(1, 33), d=st.sampled_from([8, 32, 96]), seed=st.integers(0, 5))
def test_rmsnorm_property_sweep(n, d, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    scale = jnp.asarray(rng.standard_normal(d), jnp.float32)
    out = np.asarray(rmsnorm(x, scale, block_rows=8, interpret=True))
    ref = np.asarray(rmsnorm_ref(x, scale))
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)
    # unit RMS after normalization (pre-scale) is the invariant
    y = out / np.asarray(scale)[None, :]
    np.testing.assert_allclose(np.sqrt((y**2).mean(-1)), 1.0, atol=1e-3)
