"""Fleet engine: bit-equivalence, no-recompilation, device-resident replay.

The load-bearing guarantee: driving ADFLL rounds through the vectorized
fleet engine — lazily batched, scan-fused, vmapped over agents — changes
*nothing* about round semantics. Batched flushes produce bit-identical
params, losses, history, and eval distances to sequential (flush-per-
round) driving, because the per-slot math of the fleet chunk is bitwise
invariant to how many agents share a dispatch. The legacy per-step path
(``backend="stepwise"``) is only fusion-ULPs away and keeps identical
metadata (arrival order, sim times, replay selection).
"""

import dataclasses
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.configs.adfll_dqn import ADFLLConfig, DQNConfig
from repro.core.erb import TaskTag, erb_add, erb_flatten, erb_init
from repro.core.federated import ADFLLSystem
from repro.core.replay import SelectiveReplaySampler
from repro.rl.agent import DQNAgent, dqn_step_traces, make_dqn_steps
from repro.rl.env import LandmarkEnv
from repro.rl.fleet import (
    FleetEngine,
    collect_fleet,
    make_collect_steps,
    make_fleet_steps,
)
from repro.rl.synth import make_volume, paper_eight_tasks, patient_split
from repro.telemetry import Telemetry

DQN = DQNConfig(
    volume_shape=(16, 16, 16),
    box_size=(6, 6, 6),
    conv_features=(4,),
    hidden=(32,),
    max_episode_steps=12,
    batch_size=16,
    eps_decay_steps=100,
    target_update=8,  # force target syncs inside the scanned chunk
)


def _sys_cfg(engine: str, **kw) -> ADFLLConfig:
    return ADFLLConfig(
        n_agents=2,
        agent_hub=(0, 1),
        agent_speed=(1.0, 2.0),
        rounds=2,
        train_steps_per_round=12,
        erb_capacity=512,
        erb_share_size=64,
        hub_sync_period=0.25,
        engine=engine,
        **kw,
    )


TASKS = paper_eight_tasks()
TRAIN_P, TEST_P = patient_split(16)


def _tree_equal(a, b) -> bool:
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb)
    )


def _tree_maxdiff(a, b) -> float:
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return max(
        float(np.max(np.abs(np.asarray(x) - np.asarray(y))))
        for x, y in zip(la, lb)
    )


def _run_system(engine: str, *, planes=("erb",)):
    sysm = ADFLLSystem(
        _sys_cfg(engine, share_planes=tuple(planes)), DQN, TASKS, TRAIN_P, seed=0
    )
    sysm.run()
    ev = sysm.evaluate(TASKS[:2], TEST_P)
    return sysm, ev


def _filled_erb(rng: np.random.Generator, capacity: int = 256):
    erb = erb_init(capacity, DQN.box_size, task=TaskTag("t1", "axial", "HGG"))
    n = capacity
    erb_add(
        erb,
        {
            "obs": rng.standard_normal((n, *DQN.box_size)).astype(np.float32),
            "loc": rng.random((n, 3)).astype(np.float32),
            "action": rng.integers(0, DQN.n_actions, n).astype(np.int32),
            "reward": rng.standard_normal(n).astype(np.float32),
            "next_obs": rng.standard_normal((n, *DQN.box_size)).astype(np.float32),
            "next_loc": rng.random((n, 3)).astype(np.float32),
            "done": (rng.random(n) < 0.1).astype(np.float32),
        },
    )
    return erb


# -- the tentpole guarantee --------------------------------------------------
def test_fleet_vs_sequential_bit_equivalence():
    """Same seeds -> identical params, history, and eval distance for a
    2-agent ADFLL run, batched-lazy vs flush-per-round sequential."""
    lazy, ev_lazy = _run_system("fleet")
    seq, ev_seq = _run_system("fleet-eager")
    assert any(n > 1 for n in lazy.engine.flush_sizes), "nothing batched"
    assert all(n == 1 for n in seq.engine.flush_sizes)
    for aid in lazy.agents:
        assert _tree_equal(lazy.agents[aid].params, seq.agents[aid].params)
        assert _tree_equal(
            lazy.agents[aid].target_params, seq.agents[aid].target_params
        )
    assert ev_lazy == ev_seq  # bit-identical greedy rollouts
    assert [dataclasses.astuple(r) for r in lazy.history] == [
        dataclasses.astuple(r) for r in seq.history
    ]


def test_fleet_vs_sequential_with_weight_plane():
    """Staleness-discounted weight mixing rides the same guarantee."""
    planes = ("erb", "weights")
    lazy, ev_lazy = _run_system("fleet", planes=planes)
    seq, ev_seq = _run_system("fleet-eager", planes=planes)
    assert any(r.n_mixed > 0 for r in lazy.history), "no mixing happened"
    for aid in lazy.agents:
        assert _tree_equal(lazy.agents[aid].params, seq.agents[aid].params)
    assert ev_lazy == ev_seq
    assert [dataclasses.astuple(r) for r in lazy.history] == [
        dataclasses.astuple(r) for r in seq.history
    ]


def test_fleet_vs_legacy_stepwise_semantics():
    """The legacy per-step path differs only by float-fusion ULPs: every
    RoundRecord field except the loss is identical (arrival order,
    staleness mixing, sim-time accounting unchanged)."""
    fleet, _ = _run_system("fleet")
    legacy, _ = _run_system("stepwise")
    assert legacy.engine is None
    ha = [dataclasses.astuple(r) for r in fleet.history]
    hb = [dataclasses.astuple(r) for r in legacy.history]
    assert len(ha) == len(hb)
    for ra, rb in zip(ha, hb):
        assert ra[:6] == rb[:6] and ra[7:] == rb[7:]  # all but loss exact
        assert abs(ra[6] - rb[6]) < 1e-4
    for aid in fleet.agents:
        assert (
            _tree_maxdiff(fleet.agents[aid].params, legacy.agents[aid].params) < 1e-5
        )


def test_chunk_is_bitwise_invariant_to_fleet_width():
    """One batched 3-slot flush == three 1-slot flushes, bit for bit."""
    data_rng = np.random.default_rng(7)
    erb = _filled_erb(data_rng)
    shared = FleetEngine(DQN)
    solo = [FleetEngine(DQN) for _ in range(3)]
    sampler = SelectiveReplaySampler()
    for i in range(3):
        assert shared.add_slot(seed=i) == i
        solo[i].add_slot(seed=i)
    # submit identical plans to the shared fleet and the solo engines
    futs = []
    for i in range(3):
        plan_rng = np.random.default_rng(100 + i)
        plans = [sampler.plan(plan_rng, DQN.batch_size, erb) for _ in range(9)]
        futs.append(shared.submit(i, plans))
    shared.flush()
    assert shared.flush_sizes == [3]
    for i in range(3):
        plan_rng = np.random.default_rng(100 + i)
        plans = [sampler.plan(plan_rng, DQN.batch_size, erb) for _ in range(9)]
        fut = solo[i].submit(0, plans)
        solo[i].flush()
        assert _tree_equal(shared.get_params(i), solo[i].get_params(0))
        assert _tree_equal(shared.get_target(i), solo[i].get_target(0))
        assert _tree_equal(shared.get_opt(i), solo[i].get_opt(0))
        assert futs[i].loss == fut.loss


def test_flush_on_read_and_future_resolution():
    engine = FleetEngine(DQN)
    agent = DQNAgent(0, DQN, seed=3, engine=engine)
    erb = _filled_erb(np.random.default_rng(1))
    before = agent.params
    fut = agent._submit_steps(5, erb, ())
    assert not fut.done
    seen = []
    fut.on_done(seen.append)
    after = agent.params  # read forces the flush
    assert fut.done and np.isfinite(fut.loss) and seen == [fut.loss]
    assert not _tree_equal(before, after)
    assert agent.step_count == 5


# -- no recompilation across same-config agents ------------------------------
def test_make_steps_compile_once_across_agents():
    # unique config objects so module-level caches/counters start fresh
    cfg = dataclasses.replace(DQN, eps_decay_steps=997)
    assert make_dqn_steps(cfg) is make_dqn_steps(cfg)
    assert make_fleet_steps(cfg) is make_fleet_steps(cfg)

    agents = [DQNAgent(i, cfg, seed=i, backend="stepwise") for i in range(3)]
    erb = _filled_erb(np.random.default_rng(2))
    for a in agents:
        a.train_steps(2, erb)
    assert dqn_step_traces(cfg) == 1  # one trace serves all three agents

    engine = FleetEngine(cfg)
    fleet_agents = [DQNAgent(i, cfg, seed=i, engine=engine) for i in range(3)]
    for _ in range(2):  # two identical batched flushes, one compile
        for a in fleet_agents:
            a._submit_steps(4, erb, ())
        engine.flush()
    assert engine.steps.n_traces == 1
    assert make_fleet_steps(cfg).n_traces == 1


# -- host planning == host materialization -----------------------------------
def test_sampler_plan_matches_sample():
    """plan() + materialize() is the decomposition of sample(): same rng
    stream, same rows, same shuffle."""
    rng_data = np.random.default_rng(0)
    current = _filled_erb(rng_data, 128)
    personal = [_filled_erb(rng_data, 64)]
    incoming = [_filled_erb(rng_data, 64), _filled_erb(rng_data, 32)]
    sampler = SelectiveReplaySampler()
    r1, r2 = np.random.default_rng(5), np.random.default_rng(5)
    direct = sampler.sample(r1, 32, current, personal=personal, incoming=incoming)
    plan = sampler.plan(r2, 32, current, personal=personal, incoming=incoming)
    via_plan = sampler.materialize(plan)
    assert set(direct) == set(via_plan)
    for k in direct:
        np.testing.assert_array_equal(direct[k], via_plan[k])
    # both consumed the stream identically
    assert r1.bit_generator.state == r2.bit_generator.state


# -- vectorized observation gather -------------------------------------------
def _observe_reference(env: LandmarkEnv, locs: np.ndarray) -> np.ndarray:
    """The pre-vectorization implementation: per-call np.pad + row loop."""
    b = locs.shape[0]
    bx, by, bz = env.cfg.box_size
    half = np.array([bx // 2, by // 2, bz // 2])
    pad = max(bx, by, bz)
    vol = np.pad(env.volume, pad)
    out = np.empty((b, bx, by, bz), np.float32)
    for i in range(b):
        c = locs[i] + pad - half
        out[i] = vol[c[0] : c[0] + bx, c[1] : c[1] + by, c[2] : c[2] + bz]
    return out


def test_observe_matches_loop_reference(rng):
    """Bitwise the per-row slice loop's crops, float32 and C-contiguous:
    at random locations, the corners and edges, for one row and for none,
    with a small box, a box of unequal sides, and the paper's 45^3 box on
    a volume barely larger than it."""
    for side, box in ((16, DQN.box_size), (16, (7, 4, 5)), (46, (45, 45, 45))):
        cfg = dataclasses.replace(DQN, volume_shape=(side,) * 3, box_size=box)
        vol, lm = make_volume(TaskTag("t2", "axial", "LGG"), 4, n=side)
        env = LandmarkEnv(vol, lm, cfg)
        n, m = env.n - 1, env.n // 2
        corners = np.array(np.meshgrid([0, n], [0, n], [0, n])).reshape(3, -1).T
        edges = np.array([[0, m, n], [n, 0, m], [m, n, 0], [0, 0, m], [n, n, m]])
        locs = np.concatenate(
            [rng.integers(0, env.n, size=(32, 3)), corners, edges]
        ).astype(np.int32)
        for batch in (locs, locs[-1:], locs[:0]):
            want = _observe_reference(env, batch)
            got = env.observe(batch)
            assert got.shape == (len(batch), *box)
            assert got.dtype == np.float32 and got.flags["C_CONTIGUOUS"]
            np.testing.assert_array_equal(got, want)
        # a second call exercises the pad-once cache; out= is written in place
        out = np.full((len(locs), *box), np.nan, np.float32)
        assert env.observe(locs, out=out) is out
        np.testing.assert_array_equal(out, _observe_reference(env, locs))


@pytest.mark.parametrize(
    "loc", [[-100, 0, 0], [0, 200, 0], [0, 0, -46], [0, 0, 46 + 23]]
)
def test_observe_rejects_windows_outside_the_volume(loc):
    """A location whose window leaves the zero-padded volume raises; it
    never wraps round or returns a short window."""
    cfg = dataclasses.replace(DQN, volume_shape=(46,) * 3, box_size=(45, 45, 45))
    vol, lm = make_volume(TaskTag("t2", "axial", "LGG"), 4, n=46)
    env = LandmarkEnv(vol, lm, cfg)
    with pytest.raises(IndexError):
        env.observe(np.array([[1, 2, 3], loc], np.int32))


# -- stacked collection == per-agent collection ------------------------------
def test_collect_fleet_matches_per_agent_collect():
    """One vmapped q-value dispatch per environment step for the whole
    cohort writes the same ERB bytes and leaves the same rng state as
    per-agent acting: each lane is the agent's own program on its own
    batch, and every epsilon-greedy draw comes from that agent's own
    stream in the per-agent order."""
    cfg = dataclasses.replace(DQN, max_episode_steps=8)
    engine = FleetEngine(cfg)
    fleet = [DQNAgent(i, cfg, seed=i, engine=engine) for i in range(3)]
    legacy = [DQNAgent(i, cfg, seed=i, backend="stepwise") for i in range(3)]
    task = TaskTag("t1", "axial", "HGG")
    vol, lm = make_volume(task, 2, n=16)
    erbs_f = [erb_init(256, cfg.box_size, task=task) for _ in range(3)]
    erbs_l = [erb_init(256, cfg.box_size, task=task) for _ in range(3)]
    collect_fleet(fleet, [LandmarkEnv(vol, lm, cfg) for _ in range(3)], erbs_f, 6)
    for a, erb in zip(legacy, erbs_l):
        a.collect(LandmarkEnv(vol, lm, cfg), erb, 6)
    for ef, el, fa, la in zip(erbs_f, erbs_l, fleet, legacy):
        assert ef.size == el.size > 0
        np.testing.assert_array_equal(erb_flatten(ef), erb_flatten(el))
        assert fa.rng.bit_generator.state == la.rng.bit_generator.state


def test_agent_collect_routes_through_stacked_program():
    """A lone fleet agent's ``collect`` delegates to ``collect_fleet`` and
    still matches the legacy loop exactly."""
    cfg = dataclasses.replace(DQN, max_episode_steps=8)
    engine = FleetEngine(cfg)
    fa = DQNAgent(0, cfg, seed=5, engine=engine)
    la = DQNAgent(0, cfg, seed=5, backend="stepwise")
    task = TaskTag("t2", "axial", "LGG")
    vol, lm = make_volume(task, 1, n=16)
    erb_f = erb_init(256, cfg.box_size, task=task)
    erb_l = erb_init(256, cfg.box_size, task=task)
    fa.collect(LandmarkEnv(vol, lm, cfg), erb_f, 4)
    la.collect(LandmarkEnv(vol, lm, cfg), erb_l, 4)
    assert erb_f.size == erb_l.size > 0
    np.testing.assert_array_equal(erb_flatten(erb_f), erb_flatten(erb_l))
    assert fa.rng.bit_generator.state == la.rng.bit_generator.state


def _counting(observe, rows: list):
    """``observe`` that adds the rows it crops to ``rows[0]``."""

    def counted(locs, out=None):
        rows[0] += len(locs)
        return observe(locs, out=out)

    return counted


def test_collect_fleet_matches_stepwise_when_episodes_end_early():
    """With the landmark inside the start region of a small volume, rows
    and whole agents finish early. The reused crops still give the
    stepwise agents' ERB bytes and rng states, and every observation row
    staged for a dispatch (dead rows and finished agents included) is
    the crop at the location staged beside it."""
    cfg = dataclasses.replace(
        DQN, volume_shape=(5, 5, 5), box_size=(4, 5, 3), max_episode_steps=16
    )
    vol = np.random.default_rng(11).random((5, 5, 5)).astype(np.float32)
    lm = np.array([2.0, 2.0, 2.0])
    engine = FleetEngine(cfg)
    tel = engine.telemetry = Telemetry()
    fleet = [DQNAgent(i, cfg, seed=40 + i, engine=engine) for i in range(3)]
    legacy = [DQNAgent(i, cfg, seed=40 + i, backend="stepwise") for i in range(3)]
    task = TaskTag("t1", "axial", "HGG")
    erbs_f = [erb_init(512, cfg.box_size, task=task) for _ in fleet]
    erbs_l = [erb_init(512, cfg.box_size, task=task) for _ in legacy]
    steps = make_collect_steps(cfg)
    staged = []

    def qvals(stacked, obs, loc):
        staged.append((np.array(obs), np.array(loc)))
        return qvals0(stacked, obs, loc)

    envs = [LandmarkEnv(vol, lm, cfg) for _ in fleet]
    cropped = [0]
    for env in envs:
        env.observe = _counting(env.observe, cropped)
    qvals0, steps.qvals = steps.qvals, qvals
    try:
        collect_fleet(fleet, envs, erbs_f, 4)
    finally:
        steps.qvals = qvals0
    (span,) = tel.tracer.spans("fleet.collect")
    assert span["args"]["crop_rows"] == cropped[0]
    for a, erb in zip(legacy, erbs_l):
        a.collect(LandmarkEnv(vol, lm, cfg), erb, 4)
    # rows finished mid-episode, one agent before the others, and the
    # cohort before the step budget (an ERB's version counts its appends)
    assert any(e.size < 4 * e.version for e in erbs_f)
    assert min(e.version for e in erbs_f) < len(staged) < cfg.max_episode_steps
    for ef, el, fa, la in zip(erbs_f, erbs_l, fleet, legacy):
        assert ef.size == el.size > 0
        np.testing.assert_array_equal(erb_flatten(ef), erb_flatten(el))
        assert fa.rng.bit_generator.state == la.rng.bit_generator.state
    env = LandmarkEnv(vol, lm, cfg)
    for obs, loc in staged:
        for i in range(len(fleet)):
            locs = np.rint(loc[i] * (env.n - 1)).astype(np.int32)
            np.testing.assert_array_equal(obs[i], env.observe(locs))
        assert not obs[len(fleet) :].any()  # the padding lane stays zero


# -- pow2 slot bucketing -----------------------------------------------------
def test_padded_capacity_and_dead_slot_hygiene():
    cfg = dataclasses.replace(DQN, eps_decay_steps=499)  # fresh caches
    engine = FleetEngine(cfg)
    agents = [DQNAgent(i, cfg, seed=i, engine=engine) for i in range(3)]
    assert engine.n_slots == 3 and engine.capacity == 4  # pow2 bucket
    stacked = engine.stacked_params()
    assert all(
        np.asarray(leaf).shape[0] == 3
        for leaf in jax.tree_util.tree_leaves(stacked)
    )  # dead padding rows never leak out of the engine
    erb = _filled_erb(np.random.default_rng(3))
    for a in agents:
        a._submit_steps(4, erb, ())
    # adding an agent into a spare padded row must not force a flush:
    # pending jobs keep batching across the membership change
    late = DQNAgent(3, cfg, seed=3, engine=engine)
    assert engine.n_slots == 4 and engine.capacity == 4
    assert engine.flush_sizes == []
    solo = FleetEngine(cfg)
    solo_agent = DQNAgent(0, cfg, seed=3, engine=solo)
    assert _tree_equal(engine.get_params(late.slot), solo_agent.params)
    assert engine.flush_sizes == []  # the late slot had no pending work
    _ = agents[0].params  # reading a pending slot flushes all three at once
    assert engine.flush_sizes == [3]
    # growing past the bucket boundary re-tiles to the next power of two
    DQNAgent(4, cfg, seed=4, engine=engine)
    assert engine.n_slots == 5 and engine.capacity == 8


# -- spans inside the fleet ----------------------------------------------------
FLUSH_CHILDREN = ("fleet.flush.pool", "fleet.flush.dispatch", "fleet.flush.wait")


def test_flush_children_nest_inside_the_flush_span():
    """Each flush holds one pool, one dispatch and one wait span, inside
    its own interval and together no longer than it; the flush keeps its
    attributes."""
    cfg = dataclasses.replace(DQN, eps_decay_steps=503)
    engine = FleetEngine(cfg)
    tel = engine.telemetry = Telemetry()
    agents = [DQNAgent(i, cfg, seed=i, engine=engine) for i in range(3)]
    erb = _filled_erb(np.random.default_rng(4))
    for order in (agents, agents[::-1], agents[:1]):
        for a in order:
            a._submit_steps(3, erb, ())
        engine.flush()
    flushes = tel.tracer.spans("fleet.flush")
    assert [f["args"]["jobs"] for f in flushes] == [3, 3, 1]
    for f in flushes:
        assert set(f["args"]) == {
            "jobs",
            "k_steps",
            "batch",
            "pool_rows",
            "devices",
            "resident",
            "compiled",
        }
        inside = [
            e
            for e in tel.tracer.spans()
            if e["name"] in FLUSH_CHILDREN and f["t0"] <= e["t0"] <= e["t1"] <= f["t1"]
        ]
        assert sorted(e["name"] for e in inside) == sorted(FLUSH_CHILDREN)
        assert sum(e["t1"] - e["t0"] for e in inside) <= f["t1"] - f["t0"]
    for name in FLUSH_CHILDREN:
        assert len(tel.tracer.spans(name)) == len(flushes)


def test_collect_span_counts_env_steps_and_uploads():
    """One ``fleet.collect`` span per ``collect_fleet`` call, whose
    ``env_steps`` are the rows appended to the ERBs and whose bytes are
    the staged crop and location batches of each dispatch."""
    cfg = dataclasses.replace(DQN, max_episode_steps=8, eps_decay_steps=509)
    engine = FleetEngine(cfg)
    tel = engine.telemetry = Telemetry()
    agents = [DQNAgent(i, cfg, seed=i, engine=engine) for i in range(3)]
    task = TaskTag("t1", "axial", "HGG")
    vol, lm = make_volume(task, 2, n=16)
    appended, cropped = [], []
    for episodes in (6, 4):
        erbs = [erb_init(256, cfg.box_size, task=task) for _ in agents]
        envs = [LandmarkEnv(vol, lm, cfg) for _ in agents]
        rows = [0]
        for env in envs:
            env.observe = _counting(env.observe, rows)
        collect_fleet(agents, envs, erbs, episodes)
        appended.append(sum(e.size for e in erbs))
        cropped.append(rows[0])
    spans = tel.tracer.spans("fleet.collect")
    assert len(spans) == 2
    for span, rows, crops, episodes in zip(spans, appended, cropped, (6, 4)):
        a = span["args"]
        assert a["agents"] == 3 and a["env_steps"] == rows > 0
        assert 1 <= a["dispatches"] <= cfg.max_episode_steps
        # each agent crops its start, then at most one batch per dispatch
        assert a["crop_rows"] == crops <= 3 * episodes * (a["dispatches"] + 1)
        assert a["crop_rows"] < 2 * a["env_steps"]
        # a 3-agent cohort runs as a 4-lane bucket
        staged = 4 * episodes * (np.prod(cfg.box_size) + 3) * 4
        assert a["h2d_bytes"] == a["dispatches"] * staged


def test_pool_span_counts_uploads_and_cache_hits():
    """``h2d_bytes`` on ``fleet.flush.pool`` is the bytes of the ERB rows
    uploaded on cache misses; ``erbs_cached`` counts the hits."""
    cfg = dataclasses.replace(DQN, eps_decay_steps=521)
    engine = FleetEngine(cfg)
    tel = engine.telemetry = Telemetry()
    a0, a1 = (DQNAgent(i, cfg, seed=i, engine=engine) for i in range(2))
    rng = np.random.default_rng(6)
    erb_a, erb_b = _filled_erb(rng, 128), _filled_erb(rng, 64)

    def nbytes(erb):
        return erb_flatten(erb, engine._feat)[:, None, :].nbytes

    def flush(*pairs):
        for agent, erb in pairs:
            agent._submit_steps(2, erb, ())
        engine.flush()
        return tel.tracer.spans("fleet.flush.pool")[-1]["args"]

    cold = {"erbs": 2, "erbs_cached": 0, "h2d_bytes": nbytes(erb_a) + nbytes(erb_b)}
    assert flush((a0, erb_a), (a1, erb_b)) == cold
    hits = {"erbs": 2, "erbs_cached": 2, "h2d_bytes": 0}
    assert flush((a0, erb_b), (a1, erb_a)) == hits
    erb_add(erb_b, {k: v[:8] for k, v in erb_b.data.items()})  # new version
    one_new = {"erbs": 2, "erbs_cached": 1, "h2d_bytes": nbytes(erb_b)}
    assert flush((a0, erb_a), (a1, erb_b)) == one_new


# -- device-mesh sharding (8 host-platform devices, subprocess) --------------
_MESH_SCRIPT = r"""
import numpy as np
import jax

assert jax.device_count() == 8, jax.devices()

from repro.configs.adfll_dqn import DQNConfig
from repro.core.erb import TaskTag, erb_add, erb_flatten, erb_init
from repro.core.replay import SelectiveReplaySampler
from repro.models.sharding import make_fleet_mesh
from repro.rl.agent import DQNAgent
from repro.rl.env import LandmarkEnv
from repro.rl.fleet import FleetEngine, collect_fleet
from repro.rl.synth import make_volume

CFG = DQNConfig(
    volume_shape=(16, 16, 16),
    box_size=(6, 6, 6),
    conv_features=(4,),
    hidden=(32,),
    max_episode_steps=8,
    batch_size=16,
    eps_decay_steps=100,
    target_update=8,
)


def tree_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb)
    )


mesh = make_fleet_mesh(8)
assert mesh is not None and mesh.size == 8

single = FleetEngine(CFG)
shard = FleetEngine(CFG, mesh=mesh)
a_shard = [DQNAgent(i, CFG, seed=i, engine=shard) for i in range(4)]
for i in range(4):
    single.add_slot(seed=i)
assert shard.capacity == 8  # slots padded up to the mesh size

# stacked collection under the mesh == per-agent reference acting
task = TaskTag("t1", "axial", "HGG")
vol, lm = make_volume(task, 2, n=16)
ref = [DQNAgent(i, CFG, seed=i, backend="stepwise") for i in range(4)]
erbs_m = [erb_init(256, CFG.box_size, task=task) for _ in range(4)]
erbs_r = [erb_init(256, CFG.box_size, task=task) for _ in range(4)]
collect_fleet(a_shard, [LandmarkEnv(vol, lm, CFG) for _ in range(4)], erbs_m, 4)
for a, erb in zip(ref, erbs_r):
    a.collect(LandmarkEnv(vol, lm, CFG), erb, 4)
for em, er, am, ar in zip(erbs_m, erbs_r, a_shard, ref):
    assert em.size == er.size > 0
    assert np.array_equal(erb_flatten(em), erb_flatten(er))
    assert am.rng.bit_generator.state == ar.rng.bit_generator.state

# identical plan streams: the sharded chunk is bit-identical to the
# single-device chunk, flush after flush
sampler = SelectiveReplaySampler()
data = np.random.default_rng(7)
n = 256
erb = erb_init(n, CFG.box_size, task=task)
erb_add(
    erb,
    {
        "obs": data.standard_normal((n, *CFG.box_size)).astype(np.float32),
        "loc": data.random((n, 3)).astype(np.float32),
        "action": data.integers(0, CFG.n_actions, n).astype(np.int32),
        "reward": data.standard_normal(n).astype(np.float32),
        "next_obs": data.standard_normal((n, *CFG.box_size)).astype(np.float32),
        "next_loc": data.random((n, 3)).astype(np.float32),
        "done": (data.random(n) < 0.1).astype(np.float32),
    },
)
for round_idx in range(2):
    for eng in (single, shard):
        for i in range(4):
            rng = np.random.default_rng(100 + 10 * round_idx + i)
            plans = [sampler.plan(rng, CFG.batch_size, erb) for _ in range(6)]
            eng.submit(i, plans)
        eng.flush()
    for i in range(4):
        assert tree_equal(single.get_params(i), shard.get_params(i))
        assert tree_equal(single.get_target(i), shard.get_target(i))
        assert tree_equal(single.get_opt(i), shard.get_opt(i))

# a partial flush (subset of the live slots) exercises the non-resident
# gather/scatter path under the mesh — same bit-identity guarantee
for eng in (single, shard):
    for i in range(3):
        rng = np.random.default_rng(500 + i)
        plans = [sampler.plan(rng, CFG.batch_size, erb) for _ in range(6)]
        eng.submit(i, plans)
    eng.flush()
for i in range(4):
    assert tree_equal(single.get_params(i), shard.get_params(i))

# identical flushes, one compile: explicit mesh shardings on the
# chunk's inputs/outputs must not retrace (the partial flush pads to
# the same bucket width, so it reuses the same trace)
assert shard.steps.n_traces == 1, shard.steps.n_traces
assert single.steps.n_traces == 1, single.steps.n_traces
assert shard.steps is not single.steps  # mesh-keyed cache entries

print("MESH-OK")
"""


def test_sharded_mesh_bit_identity_and_no_recompile():
    """The 8-device assertions must run in a subprocess: the host-platform
    device count only takes effect when set before jax initializes, and
    conftest pins this process to one CPU device."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()
    env["JAX_PLATFORMS"] = "cpu"
    env.setdefault("PYTHONPATH", "src")
    proc = subprocess.run(
        [sys.executable, "-c", _MESH_SCRIPT],
        capture_output=True,
        text=True,
        env=env,
        timeout=600,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    assert "MESH-OK" in proc.stdout


def test_fleet_mesh_never_shrinks_silently():
    from repro.models.sharding import make_fleet_mesh

    n = len(jax.devices())
    assert make_fleet_mesh() is None or make_fleet_mesh().size <= n
    assert make_fleet_mesh(1) is None
    for bad in (n + 1, 2 * n, 3):  # more than visible, or not a power of two
        with pytest.raises(ValueError):
            make_fleet_mesh(bad)


def test_agent_sampler_inherits_use_pallas_flag():
    agent = DQNAgent(0, DQN, seed=0, backend="stepwise")
    assert agent.sampler.use_pallas is False
    agent_p = DQNAgent(1, DQN, seed=1, backend="stepwise", use_pallas=True)
    assert agent_p.sampler.use_pallas is True
