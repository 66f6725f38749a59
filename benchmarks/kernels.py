"""Kernel microbenchmarks: Pallas vs pure-jnp oracle.

The kernels run compiled on TPU and, on any other backend, under the
Pallas interpreter — asked for explicitly here, and named
in every row (``_interp`` / ``_compiled``). Interpret-mode timings are
NOT TPU performance: on CPU the value is correctness at benchmark shapes.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np


def _time(fn, *args, iters=3):
    out = fn(*args)  # warmup / compile
    (out[0] if isinstance(out, tuple) else out).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
        (out[0] if isinstance(out, tuple) else out).block_until_ready()
    return (time.perf_counter() - t0) / iters * 1e6


def _row(name, us, ref_us, err):
    return (name, us, f"ref_us={ref_us:.0f};max_err={err:.2e}")


def run():
    rng = np.random.default_rng(0)
    rows = []
    interpret = jax.default_backend() != "tpu"  # compiled kernels exist only on TPU
    tag = "interp" if interpret else "compiled"

    # flash attention (modest shape; interpret mode is a python loop)
    b, s, hq, hkv, d = 1, 256, 4, 2, 64
    q = jnp.asarray(rng.standard_normal((b, s, hq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, hkv, d)), jnp.float32)
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref

    def fa(a, b_, c):
        return flash_attention(a, b_, c, block_q=128, block_k=128, interpret=interpret)

    t_pl = _time(fa, q, k, v)
    t_ref = _time(jax.jit(attention_ref), q, k, v)
    err = float(jnp.max(jnp.abs(fa(q, k, v) - attention_ref(q, k, v))))
    rows.append(_row(f"flash_attention_{tag}", t_pl, t_ref, err))

    # replay gather
    from repro.kernels.replay_gather.ops import replay_gather
    from repro.kernels.replay_gather.ref import replay_gather_ref

    buf = jnp.asarray(rng.standard_normal((4096, 1, 512)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, 4096, 256), jnp.int32)
    w = jnp.ones((256,), jnp.float32)
    mode = "interpret" if interpret else "compiled"

    def rg(b_, i, w_):
        return replay_gather(b_, i, w_, mode=mode)

    t_pl = _time(rg, buf, idx, w)
    t_ref = _time(jax.jit(replay_gather_ref), buf, idx, w)
    err = float(jnp.max(jnp.abs(rg(buf, idx, w) - replay_gather_ref(buf, idx, w))))
    rows.append(_row(f"replay_gather_{tag}", t_pl, t_ref, err))

    # fused td
    from repro.kernels.fused_td.kernel import fused_td
    from repro.kernels.fused_td.ref import fused_td_ref

    qs = jnp.asarray(rng.standard_normal((1024, 1)), jnp.float32)
    qn = jnp.asarray(rng.standard_normal((1024, 6)), jnp.float32)
    r = jnp.asarray(rng.standard_normal((1024, 1)), jnp.float32)
    dn = jnp.zeros((1024, 1), jnp.float32)
    f_pl = jax.jit(lambda *a: fused_td(*a, gamma=0.9, interpret=interpret)[0])
    f_ref = jax.jit(lambda *a: fused_td_ref(*a, gamma=0.9)[0])
    t_pl = _time(f_pl, qs, qn, r, dn)
    t_ref = _time(f_ref, qs, qn, r, dn)
    err = float(jnp.max(jnp.abs(f_pl(qs, qn, r, dn) - f_ref(qs, qn, r, dn))))
    rows.append(_row(f"fused_td_{tag}", t_pl, t_ref, err))

    # fused rmsnorm
    from repro.kernels.rmsnorm.ops import rmsnorm
    from repro.kernels.rmsnorm.ref import rmsnorm_ref

    x = jnp.asarray(rng.standard_normal((2048, 768)), jnp.float32)
    sc = jnp.asarray(rng.standard_normal((768,)), jnp.float32)
    def rn(x_, s_):
        return rmsnorm(x_, s_, interpret=interpret)

    t_pl = _time(rn, x, sc)
    t_ref = _time(jax.jit(rmsnorm_ref), x, sc)
    err = float(jnp.max(jnp.abs(rn(x, sc) - rmsnorm_ref(x, sc))))
    rows.append(_row(f"rmsnorm_{tag}", t_pl, t_ref, err))
    for name, us, derived in rows:
        print(f"{name},{us:.0f},{derived}")
    return rows


if __name__ == "__main__":
    run()
