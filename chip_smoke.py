"""Smoke run of the ADFLL fleet on a TPU: does the main path start on the chip?

    python3 chip_smoke.py               # one chip: train + serve
    python3 chip_smoke.py --four-chip   # four chips: sharded fleet vs one device

One chip (the default) runs three phases in this one process:

* kernels — the compiled Pallas kernels (replay gather at 8^3 and 45^3
  row widths, fused TD loss and its gradient) against their XLA oracles
  on small random inputs.

* train — through the entry point a user calls,
  ``repro.experiments.runner.run("paper_fig2")`` at its
  registered size (4 asynchronous agents, 3 hubs, 3 rounds x 80 train
  steps, collection, ERB sharing, evaluation). Checks: the mean distance
  error is finite, the round count is what the spec implies, the fleet
  flushed, and every flush ran the TPU's replay gather
  (``replay_gather.ops.TPU_MODE``).
* serve — a ``build_session`` / ``run_session`` serving session, 48
  requests in two waves around one train-and-publish hot swap. Checks: no
  recompilation after warm-up, every request answered, one swap.

``--four-chip`` runs only the sharded fleet: ``paper_fig2`` widened to 64
agents with ``fleet_devices=4`` through the runner (agent shards on 4
distinct devices, a few flushes), then the fleet's final state through
one flush and one collection step on the mesh and on one device. The
mesh programs must be bitwise equal to the one-device program run shard
by shard (16 agents at a time: what each chip runs). Against one program
over all 64 agents they agree only to float rounding — the TPU's
arithmetic depends on how many agents one vmapped program holds — so
that comparison is printed and bounded, not bitwise.

The numbers printed are from a smoke run (set-up and compile seconds of
each phase), not a benchmark. The last line of standard output is one
JSON object, ``{"ok": true, "device": {...}}``, printed only when every
check passed. Without a TPU the script exits non-zero before any phase:
it never falls back to the CPU.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time
from dataclasses import replace
from pathlib import Path

import jax
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))


class SmokeFailure(Exception):
    """A check of the smoke run failed."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)
    print(f"  check ok: {what}")


class CompileClock:
    """Seconds XLA spent compiling, from JAX's own monitoring events."""

    def __init__(self):
        self.seconds = 0.0
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.n += 1


def system_probe():
    """An experiment hook that keeps a handle on the system it ran in."""
    from repro.core.experiment import ExperimentHooks

    class SystemProbe(ExperimentHooks):
        system = None

        def on_round_start(self, system, *args) -> None:
            self.system = system

    return SystemProbe()


def same_bits(a, b) -> bool:
    """Whether two pytrees of arrays are bitwise equal, leaf by leaf."""
    pairs = zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b), strict=True)
    return all(np.array_equal(np.asarray(x), np.asarray(y)) for x, y in pairs)


def timed_phase(name: str, clock: CompileClock, fn):
    c0, n0, t0 = clock.seconds, clock.n, time.perf_counter()
    out = fn()
    wall = time.perf_counter() - t0
    print(
        f"[{name}] smoke-run timing (not a benchmark): wall {wall:.2f} s, "
        f"of which XLA compile {clock.seconds - c0:.2f} s over "
        f"{clock.n - n0} programs"
    )
    return out


def train_phase(expect_gather: str) -> None:
    from repro.experiments.runner import resolve, run

    spec = resolve("paper_fig2")
    probe = system_probe()
    report = run(spec, hooks=(probe,))
    engine = probe.system.engine
    steps = engine.steps
    print(
        f"  paper_fig2: mean_dist_err={report.mean_dist_err} "
        f"n_rounds={report.n_rounds} flushes={engine.n_flushes} "
        f"steps_trained={engine.n_steps_trained} chunk_traces={steps.n_traces}"
    )
    print(f"  replay gather run by the flushes: {steps.gather_mode}")
    check(math.isfinite(report.mean_dist_err), "mean_dist_err is finite")
    n_rounds = spec.sys.n_agents * spec.sys.rounds
    check(report.n_rounds == n_rounds, f"n_rounds == {n_rounds}")
    check(engine.n_flushes >= 1, "the fleet flushed")
    check(
        engine.n_steps_trained == n_rounds * spec.sys.train_steps_per_round,
        "every round's train steps ran",
    )
    check(steps.gather_mode == expect_gather, f"gather is {expect_gather!r}")


def kernel_phase(gather_mode: str) -> None:
    """The TPU kernels against their XLA oracles on small inputs."""
    import jax.numpy as jnp

    from repro.core.erb import flat_width
    from repro.kernels.fused_td.ops import td_loss
    from repro.kernels.fused_td.ref import fused_td_ref
    from repro.kernels.replay_gather.ops import pool_width, replay_gather

    rng = np.random.default_rng(0)
    for box in (8, 45):
        width = pool_width(flat_width((box,) * 3))
        pool = jnp.asarray(rng.standard_normal((64, 1, width), np.float32))
        idx = jnp.asarray(rng.integers(0, 64, 32), jnp.int32)
        w = jnp.asarray(rng.random(32, np.float32))
        out = replay_gather(pool, idx, w, mode=gather_mode)
        ref = replay_gather(pool, idx, w, mode="ref")
        check(
            bool(jnp.array_equal(out, ref)),
            f"{gather_mode} replay_gather == XLA gather at {box}^3 rows",
        )
    q_sel, r = (jnp.asarray(rng.standard_normal((32, 1), np.float32)) for _ in "ab")
    q_next = jnp.asarray(rng.standard_normal((32, 6), np.float32))
    done = jnp.asarray(rng.random((32, 1)) < 0.3, jnp.float32)
    interpret = gather_mode == "interpret"  # CPU rehearsals of this phase
    grad = jax.jit(
        jax.grad(lambda q: td_loss(q, q_next, r, done, 0.9, True, interpret))
    )
    got = grad(q_sel)

    def ref_loss(q):
        return jnp.mean(fused_td_ref(q, q_next, r, done, gamma=0.9)[0])

    want = jax.jit(jax.grad(ref_loss))(q_sel)
    err = float(jnp.max(jnp.abs(got - want)))
    print(f"  fused_td kernel grad vs XLA autodiff: max |diff| = {err}")
    check(err <= 1e-6, "fused_td kernel gradient matches the XLA oracle")


def serve_phase() -> None:
    from repro.experiments.runner import resolve
    from repro.serve import TrafficSpec, build_session, run_session

    cfg = resolve("paper_fig2").dqn
    traffic = TrafficSpec(n_requests=48, max_batch=8)
    session = build_session(cfg, n_agents=4, traffic=traffic, seed=0)
    report = run_session(session, traffic, n_waves=2, train_steps=20)
    s = report.summary()
    print(
        f"  served {s['n_requests']} requests, swaps={s['n_swaps']}, "
        f"versions={s['versions_served']}, recompiles={s['recompiles']}, "
        f"mean_dist_err={s['mean_dist_err']}"
    )
    check(report.recompiles == 0, "recompiles == 0 after warm-up")
    answered = [r for r in report.requests if r.final_loc is not None]
    check(
        len(answered) == traffic.n_requests,
        f"all {traffic.n_requests} requests answered",
    )
    check(report.n_swaps >= 1, "one train-and-publish hot swap")


def four_chip_phase(n_agents: int = 64, n_devices: int = 4) -> None:
    """The sharded fleet through the system, then its programs against the
    same fleet on one device, from identical inputs."""
    from repro.core.erb import flat_width
    from repro.experiments.runner import resolve, run
    from repro.kernels.replay_gather.ops import pool_width
    from repro.rl.fleet import make_collect_steps, make_fleet_steps

    base = resolve("paper_fig2")
    sys_cfg = replace(
        base.sys,
        n_agents=n_agents,
        agent_hub=tuple(base.sys.agent_hub[i % 4] for i in range(n_agents)),
        agent_speed=tuple(base.sys.agent_speed[i % 4] for i in range(n_agents)),
        rounds=3,
        fleet_devices=n_devices,
    )
    spec = replace(base, sys=sys_cfg, eval_tasks=1, eval_patients=1, eval_episodes=1)
    probe = system_probe()
    report = run(spec, hooks=(probe,))
    engine = probe.system.engine
    mesh = engine.mesh
    holders = sorted({str(s.device) for s in engine.state.count.addressable_shards})
    print(f"  mesh: {mesh.size} devices on axis {mesh.axis_names}; agent shards on")
    print(f"    {holders}")
    print(
        f"  {n_agents} agents: flushes={engine.n_flushes} "
        f"mean_dist_err={report.mean_dist_err} gather={engine.steps.gather_mode}"
    )
    check(mesh.size == n_devices, f"mesh holds {n_devices} devices")
    check(len(holders) == n_devices, f"agent shards on {n_devices} distinct devices")
    check(engine.n_flushes >= 3, "a few sharded flushes")
    check(math.isfinite(report.mean_dist_err), "mean_dist_err is finite")

    # the fleet's final state, one flush of K steps on random replay rows:
    # on the mesh, on one device shard by shard (the per-device program),
    # and on one device in a single program over every agent
    cfg, fs = engine.cfg, engine.sharding
    n, per = engine.capacity, engine.capacity // mesh.size
    host = jax.device_get(engine.state)
    rng = np.random.default_rng(0)
    width = pool_width(flat_width(cfg.box_size))
    pool = rng.standard_normal((512, 1, width), np.float32)
    idx = rng.integers(0, 512, (8, n, cfg.batch_size)).astype(np.int32)
    on_mesh = jax.device_get(
        engine.steps.train_chunk(
            fs.place(host),
            jax.device_put(pool, fs.replicated),
            jax.device_put(idx, fs.indices),
        )
    )
    one = make_fleet_steps(cfg)

    def shard(tree, i):
        return jax.tree_util.tree_map(lambda x: x[i * per : (i + 1) * per], tree)

    shards = [
        jax.device_get(
            one.train_chunk(shard(host, i), pool, idx[:, i * per : (i + 1) * per])
        )
        for i in range(mesh.size)
    ]
    by_shard = (
        jax.tree_util.tree_map(lambda *xs: np.concatenate(xs), *[s[0] for s in shards]),
        np.concatenate([s[1] for s in shards], axis=1),
    )
    whole = jax.device_get(one.train_chunk(host, pool, idx))
    check(
        same_bits(on_mesh, by_shard),
        f"sharded chunk bitwise equal to the one-device program on {per}-agent shards",
    )
    loss_m, loss_1 = on_mesh[1][0], whole[1][0]
    rel = float(np.max(np.abs(loss_m - loss_1)) / np.max(np.abs(loss_1)))
    leaves = zip(
        jax.tree_util.tree_leaves(on_mesh[0].params),
        jax.tree_util.tree_leaves(whole[0].params),
        strict=True,
    )
    p_diff = max(float(np.max(np.abs(a - b))) for a, b in leaves)
    print(
        f"  vs one program over all {n} agents on one device: first-step loss "
        f"max rel |diff| = {rel}, params after {idx.shape[0]} steps max |diff| = "
        f"{p_diff}"
    )
    check(rel <= 1e-4, "first-step losses agree with the one-device program")

    obs = rng.standard_normal((n, 16, *cfg.box_size), np.float32)
    loc = rng.random((n, 16, 3), np.float32)
    q_mesh = np.asarray(
        make_collect_steps(cfg, mesh=mesh).qvals(
            fs.place(host.params), fs.place(obs), fs.place(loc)
        )
    )
    qv = make_collect_steps(cfg).qvals
    q_shards = np.concatenate(
        [np.asarray(qv(*shard((host.params, obs, loc), i))) for i in range(mesh.size)]
    )
    q_whole = np.asarray(qv(host.params, obs, loc))
    same_act = float(np.mean(q_mesh.argmax(-1) == q_whole.argmax(-1)))
    print(
        f"  collect q-values vs one program over all agents: max |diff| = "
        f"{float(np.max(np.abs(q_mesh - q_whole)))}, same greedy action {same_act}"
    )
    check(
        same_bits(q_mesh, q_shards),
        "sharded collect q-values bitwise equal to the one-device program on shards",
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--four-chip",
        action="store_true",
        help="run only the sharded-fleet phase on a 4-chip mesh",
    )
    args = ap.parse_args(argv)
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: JAX found no usable backend: {e}", file=sys.stderr)
        return 1
    dev = devices[0]
    n = len(devices)
    print(f"device: platform={dev.platform} kind={dev.device_kind} count={n}")
    if dev.platform != "tpu":
        print("chip_smoke: no TPU visible; not running on the CPU", file=sys.stderr)
        return 1
    want = 4 if args.four_chip else 1
    if n < want:
        print(f"chip_smoke: needs {want} chips, sees {n}", file=sys.stderr)
        return 1

    from repro.compile_cache import enable_compile_cache
    from repro.kernels.replay_gather.ops import TPU_MODE

    print(f"compile cache: {enable_compile_cache()}")
    clock = CompileClock()
    try:
        if args.four_chip:
            timed_phase("four-chip", clock, four_chip_phase)
        else:
            timed_phase("kernels", clock, lambda: kernel_phase(TPU_MODE))
            timed_phase("train", clock, lambda: train_phase(TPU_MODE))
            timed_phase("serve", clock, serve_phase)
    except SmokeFailure as e:
        print(f"chip_smoke: check failed: {e}", file=sys.stderr)
        return 1
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": n}
    result = {"ok": True, "device": device}
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
