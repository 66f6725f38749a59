"""Vectorized fleet engine: stacked-agent pytrees, scan-fused training,
and device-resident replay.

The ADFLL simulator used to execute its fleet one agent at a time: a
fresh ``jax.jit`` per agent, one dispatch per training step, and a
blocking ``float(loss)`` host sync after every update — N agents x K
steps = N*K dispatches per round of rounds. This module turns that into
*one* compiled program stepping many agents at once:

* :class:`FleetState` — every agent's params / target params / optimizer
  state / PRNG key / step counter as one stacked pytree with a leading
  agent axis.
* :func:`make_fleet_steps` — a module-level, (config, mesh)-keyed cache
  of the compiled fleet program. The train chunk is ``lax.scan``-fused
  over the K inner steps of a round and ``vmap``-ed over the agent axis,
  so a flush of J pending rounds is a single dispatch. Buffers are
  donated on accelerators (donation is a no-op on CPU).
* Fleet-axis sharding: given a 1-D device mesh
  (:func:`repro.models.sharding.make_fleet_mesh`), the stacked agent
  axis is partitioned across devices (state and indices sharded on the
  agent axis, replay pool replicated) and the chunk is one ``shard_map``
  program over that axis — per-agent work is embarrassingly parallel, so
  each device trains its own slots with no collective at all (left to
  the automatic partitioner, the TPU compiler splits the vmapped convs
  across devices and inserts all-gathers). The engine pads its resident
  slot count to a mesh-divisible pow2 bucket (dead slots are inert
  copies, never read), and a flush that covers the whole bucket skips
  the gather/scatter entirely: the resident state flows through the
  donated chunk end to end.
* :func:`collect_fleet` — the *collection* phase batched the same way: a
  stacked greedy-rollout program (:class:`CollectSteps`) computes every
  cohort agent's q-values for its own episode batch in ONE vmapped
  dispatch per environment step, replacing per-agent ``q_values``
  round-trips. Each lane applies its agent's params to its own ``[B]``
  batch — the identical slot program — so stacked collection is
  bit-identical to per-agent acting.
* Device-resident replay: ERBs are cached on device as ``[size, 1, W]``
  float32 row views (``W`` = the flat row width padded to whole TPU
  lanes, see ``kernels/replay_gather/ops.py``); the host
  :class:`~repro.core.replay.SelectiveReplaySampler`
  shrinks to pool/index *selection* (its ``plan()`` half), and batch
  materialization happens inside the compiled chunk through
  ``replay_gather`` (the compiled Pallas kernel on TPU, XLA's gather
  elsewhere: :attr:`FleetSteps.gather_mode`) — one stacked host->device index
  transfer per scan chunk instead of one batch transfer per step.
* :class:`FleetEngine` — the host-side orchestrator: slots, lazy job
  queue, flush-on-read semantics. ``DQNAgent`` is a thin view over a
  slot; ``ADFLLSystem`` submits rounds and lets reads force batched
  flushes.

Numerics: the per-slot math of the fleet chunk is bitwise invariant to
the number of agents batched together (vmap slots are independent and
XLA:CPU compiles the slot program identically for any leading axis — see
``tests/test_fleet.py``), which is what makes the fleet-vs-sequential
bit-equivalence guarantee testable. The *legacy* per-step dispatch path
(``DQNAgent(backend="stepwise")``) differs from the fused program by
float-fusion ULPs, so it is kept only as a baseline and for
benchmarking.
"""

from __future__ import annotations

from collections import OrderedDict
from collections.abc import Callable, Sequence
from dataclasses import dataclass
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.adfll_dqn import DQNConfig
from repro.core.erb import ERB, erb_add, erb_flatten, flat_width
from repro.kernels.fused_td.ops import td_loss
from repro.kernels.replay_gather.ops import pool_width, replay_gather, resolve_mode
from repro.models.sharding import FleetSharding
from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update
from repro.rl.dqn import dqn_apply, dqn_init
from repro.telemetry import NULL


@jax.tree_util.register_pytree_node_class
@dataclass
class FleetState:
    """Stacked per-agent training state, leading axis = agent slot."""

    params: Any  # [N, ...] stacked DQN parameter pytree
    target: Any  # [N, ...] stacked target-network pytree
    opt: Any  # [N, ...] stacked AdamW state ({m, v, count})
    rng: jax.Array  # [N, 2] uint32 per-slot PRNG keys
    count: jax.Array  # [N] int32 per-slot step counters (target sync)

    def tree_flatten(self):
        return (self.params, self.target, self.opt, self.rng, self.count), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)

    @property
    def n_slots(self) -> int:
        return int(self.count.shape[0])


def make_dqn_opt_cfg(cfg: DQNConfig) -> AdamWConfig:
    """The DQN optimizer settings — one definition for the fleet chunk
    and the legacy per-step path (they must stay numerically twinned)."""
    return AdamWConfig(
        lr=cfg.lr, weight_decay=0.0, clip_norm=10.0, warmup_steps=0, total_steps=10**9
    )


def make_dqn_loss_fn(cfg: DQNConfig, use_pallas: bool):
    """The TD loss on a minibatch dict — shared by the fleet chunk and
    the legacy per-step path."""

    def loss_fn(params, target_params, batch):
        q = dqn_apply(cfg, params, batch["obs"], batch["loc"])
        q_sel = jnp.take_along_axis(q, batch["action"][:, None], 1)
        q_next = dqn_apply(cfg, target_params, batch["next_obs"], batch["next_loc"])
        q_next = jax.lax.stop_gradient(q_next)
        return td_loss(
            q_sel,
            q_next,
            batch["reward"][:, None],
            batch["done"][:, None],
            cfg.gamma,
            use_pallas,
        )

    return loss_fn


class FleetSteps:
    """The compiled fleet program for one (config, use_pallas, mesh) triple.

    ``train_chunk(state_slice, pool, idx) -> (state_slice, losses)`` where
    ``state_slice`` is a :class:`FleetState` of the participating slots,
    ``pool`` is the ``[R, 1, W]`` device replay pool shared by the
    chunk, and ``idx`` is the ``[K, N, B]`` int32 global row-index tensor
    (the one host->device transfer of a flush). ``n_traces`` counts
    retraces — the no-recompilation tests assert it stays at 1 across
    same-config agents.

    ``train_chunk_stats`` is the observatory variant: the same scan with
    the same update math, additionally carrying a small stacked stats
    pytree (per-step per-slot loss / mean |TD error| / max |Q| / grad
    global-norm, plus a per-slot params-finite flag) through the scan —
    accumulated device-side and drained only at the flush boundary, so
    enabling the observatory adds no extra host syncs.  It is compiled
    lazily on first use: engines without an observatory never trace it.

    With a ``mesh`` (1-D agent-axis device mesh), both chunks are
    ``shard_map`` programs jitted with explicit in/out shardings: state
    leaves and the ``[K, N, B]`` index tensor partitioned on the agent
    axis, the replay pool replicated. Each device runs the slot program
    on its own shard — agents-per-device throughput scaling. On CPU the
    per-slot math is bitwise identical to a single-device run (the same
    N-invariance that backs the fleet-vs-sequential guarantee; asserted
    in ``tests/test_fleet.py``); on TPU the compiled program depends on
    the lane count, so the mesh matches one device only shard by shard.

    ``gather_mode`` names the replay gather the chunks run
    (:func:`repro.kernels.replay_gather.ops.resolve_mode` of ``"auto"``).
    """

    def __init__(self, cfg: DQNConfig, use_pallas: bool, mesh=None):
        self.cfg = cfg
        self.use_pallas = use_pallas
        self.mesh = mesh
        self.sharding = FleetSharding(mesh) if mesh is not None else None
        self.opt_cfg = make_dqn_opt_cfg(cfg)
        self.n_traces = 0
        # the replay gather this program runs: the backend's named
        # choice (compiled Pallas kernel on TPU, XLA gather elsewhere)
        self.gather_mode = resolve_mode("auto")
        box = cfg.box_size
        obs_f = box[0] * box[1] * box[2]
        feat = pool_width(flat_width(box))

        def split_rows(rows):
            """[B, feat] pool rows -> batch dict (FLAT_FIELDS column
            order; the zero lanes past the last field are ignored)."""
            b = rows.shape[0]
            o = 0
            out = {}
            for key, width in (
                ("obs", obs_f),
                ("loc", 3),
                ("action", 1),
                ("reward", 1),
                ("next_obs", obs_f),
                ("next_loc", 3),
                ("done", 1),
            ):
                v = rows[:, o : o + width]
                o += width
                if key in ("obs", "next_obs"):
                    v = v.reshape(b, *box)
                elif key in ("action", "reward", "done"):
                    v = v[:, 0]
                if key == "action":
                    v = v.astype(jnp.int32)
                out[key] = v
            return out

        def gather_batch(pool, idx_k):
            """[R, 1, feat] pool, [N, B] row indices -> stacked batch dict."""
            n, b = idx_k.shape
            rows = replay_gather(
                pool,
                idx_k.reshape(-1),
                jnp.ones((n * b,), jnp.float32),
                mode=self.gather_mode,
            )
            return jax.vmap(split_rows)(rows.reshape(n, b, feat))

        loss_fn = make_dqn_loss_fn(cfg, use_pallas)

        def slot_step(params, target, opt, count, batch):
            loss, grads = jax.value_and_grad(loss_fn)(params, target, batch)
            params, opt, _ = adamw_update(self.opt_cfg, params, grads, opt)
            count = count + 1
            sync = (count % cfg.target_update) == 0
            target = jax.tree_util.tree_map(
                lambda t, p: jnp.where(sync, p, t), target, params
            )
            return params, target, opt, count, loss

        def loss_fn_stats(params, target_params, batch):
            # the same primal graph as loss_fn, with observational
            # scalars as a non-differentiated aux output
            q = dqn_apply(cfg, params, batch["obs"], batch["loc"])
            q_sel = jnp.take_along_axis(q, batch["action"][:, None], 1)
            q_next = dqn_apply(cfg, target_params, batch["next_obs"], batch["next_loc"])
            q_next = jax.lax.stop_gradient(q_next)
            loss = td_loss(
                q_sel,
                q_next,
                batch["reward"][:, None],
                batch["done"][:, None],
                cfg.gamma,
                use_pallas,
            )
            td_target = batch["reward"][:, None] + cfg.gamma * (
                1.0 - batch["done"][:, None]
            ) * jnp.max(q_next, axis=-1, keepdims=True)
            td_abs = jnp.mean(jnp.abs(jax.lax.stop_gradient(q_sel) - td_target))
            q_max = jnp.max(jnp.abs(jax.lax.stop_gradient(q)))
            return loss, (td_abs, q_max)

        def slot_step_stats(params, target, opt, count, batch):
            (loss, aux), grads = jax.value_and_grad(loss_fn_stats, has_aux=True)(
                params, target, batch
            )
            params, opt, _ = adamw_update(self.opt_cfg, params, grads, opt)
            count = count + 1
            sync = (count % cfg.target_update) == 0
            target = jax.tree_util.tree_map(
                lambda t, p: jnp.where(sync, p, t), target, params
            )
            gnorm = jnp.sqrt(
                sum(jnp.sum(g * g) for g in jax.tree_util.tree_leaves(grads))
            )
            td_abs, q_max = aux
            return params, target, opt, count, loss, td_abs, q_max, gnorm

        def chunk(state: FleetState, pool, idx):
            self.n_traces += 1  # trace-time side effect: counts retraces

            def body(carry, idx_k):
                p, t, o, c = carry
                batch = gather_batch(pool, idx_k)
                p, t, o, c, loss = jax.vmap(slot_step)(p, t, o, c, batch)
                return (p, t, o, c), loss

            carry = (state.params, state.target, state.opt, state.count)
            (p, t, o, c), losses = jax.lax.scan(body, carry, idx)
            rng = jax.vmap(jax.random.fold_in)(state.rng, c)
            return FleetState(p, t, o, rng, c), losses

        def chunk_stats(state: FleetState, pool, idx):
            self.n_traces += 1  # trace-time side effect: counts retraces

            def body(carry, idx_k):
                p, t, o, c = carry
                batch = gather_batch(pool, idx_k)
                p, t, o, c, loss, td, qm, gn = jax.vmap(slot_step_stats)(
                    p, t, o, c, batch
                )
                return (p, t, o, c), (loss, td, qm, gn)

            carry = (state.params, state.target, state.opt, state.count)
            (p, t, o, c), (losses, td, qm, gn) = jax.lax.scan(body, carry, idx)
            rng = jax.vmap(jax.random.fold_in)(state.rng, c)
            finite = jnp.ones((c.shape[0],), bool)
            for leaf in jax.tree_util.tree_leaves(p):
                finite = finite & jnp.all(
                    jnp.isfinite(leaf.reshape(leaf.shape[0], -1)), axis=1
                )
            stats = {
                "loss": losses,  # [K, N]
                "td_abs": td,  # [K, N]
                "q_max": qm,  # [K, N]
                "grad_norm": gn,  # [K, N]
                "params_finite": finite,  # [N]
            }
            return FleetState(p, t, o, rng, c), stats

        # donated stacked buffers: in-place update on accelerators
        # (donation is unimplemented on CPU; avoid the warning spam there)
        self._donate = () if jax.default_backend() == "cpu" else (0,)
        self.train_chunk: Callable = self._jit(chunk, "indices")
        self._chunk_stats_fn = chunk_stats
        self._train_chunk_stats: Callable | None = None

    def _jit(self, chunk: Callable, out_kind) -> Callable:
        """Jit a chunk. On a mesh the chunk is one ``shard_map`` program
        over the fleet axis: each device runs the slot program on its own
        agents against its replica of the pool, so the compiler partitions
        nothing — no collective is inserted, and Pallas kernels (which
        cannot be partitioned automatically) stay legal inside.
        ``out_kind`` names the sharding of the chunk's second output, a
        :class:`FleetSharding` property or a dict of them."""
        if self.sharding is None:
            return jax.jit(chunk, donate_argnums=self._donate)
        fs = self.sharding
        in_sh = (fs.stacked, fs.replicated, fs.indices)
        out_sh = (fs.stacked, jax.tree_util.tree_map(partial(getattr, fs), out_kind))

        def specs(tree):
            return jax.tree_util.tree_map(lambda sh: sh.spec, tree)

        # check_vma=False: the Pallas kernels' output shapes carry no
        # varying-axis annotation, and a program with no collective has
        # nothing for that checker to check
        body = jax.shard_map(
            chunk,
            mesh=self.mesh,
            in_specs=specs(in_sh),
            out_specs=specs(out_sh),
            check_vma=False,
        )
        return jax.jit(
            body,
            donate_argnums=self._donate,
            in_shardings=in_sh,
            out_shardings=out_sh,
        )

    @property
    def train_chunk_stats(self) -> Callable:
        """The stats-carrying chunk, jitted on first use (engines without
        an observatory never pay its trace/compile)."""
        if self._train_chunk_stats is None:
            self._train_chunk_stats = self._jit(
                self._chunk_stats_fn,
                {
                    "loss": "indices",  # [K, N]
                    "td_abs": "indices",
                    "q_max": "indices",
                    "grad_norm": "indices",
                    "params_finite": "stacked",  # [N]
                },
            )
        return self._train_chunk_stats

    def init_slot(self, seed: int) -> FleetState:
        """A 1-slot :class:`FleetState` seeded exactly like the legacy
        ``DQNAgent.__post_init__`` (``dqn_init(PRNGKey(seed))``)."""
        key = jax.random.PRNGKey(seed)
        params = dqn_init(key, self.cfg)
        opt = adamw_init(self.opt_cfg, params)

        def one(x):
            return jax.tree_util.tree_map(lambda v: jnp.asarray(v)[None], x)

        return FleetState(
            params=one(params),
            target=one(params),
            opt=one(opt),
            rng=jax.random.fold_in(key, 1)[None],
            count=jnp.zeros((1,), jnp.int32),
        )


_FLEET_STEPS_CACHE: dict[tuple, FleetSteps] = {}


def make_fleet_steps(cfg: DQNConfig, *, use_pallas: bool = False, mesh=None) -> FleetSteps:
    """(config, mesh)-keyed cache of the compiled fleet program: N
    same-config agents (or engines) share one traced/compiled
    ``train_chunk``. ``jax.sharding.Mesh`` is hashable, so meshed and
    single-device engines coexist without retracing each other."""
    key = (cfg, bool(use_pallas), mesh)
    steps = _FLEET_STEPS_CACHE.get(key)
    if steps is None:
        steps = FleetSteps(cfg, bool(use_pallas), mesh)
        _FLEET_STEPS_CACHE[key] = steps
    return steps


class CollectSteps:
    """The compiled stacked greedy-rollout q-value program of one config.

    ``qvals(stacked, obs, loc) -> q`` maps an ``[A, ...]`` stacked
    parameter pytree and ``[A, B, *box]`` / ``[A, B, 3]`` per-agent
    observation batches to ``[A, B, n_actions]`` q-values: one vmapped
    dispatch computes every cohort agent's greedy preferences for the
    step, replacing A per-agent ``q_values`` round-trips during
    collection. Each lane is ``dqn_apply`` on that agent's own ``[B]``
    batch — the exact per-agent program — so the stacked q-values are
    bitwise identical to per-agent acting (asserted in
    ``tests/test_fleet.py``), and epsilon-greedy sampling stays on the
    host consuming each agent's own rng stream in the per-agent order.

    With a ``mesh``, all three operands are sharded on the leading agent
    axis and the program is a ``shard_map`` over it, so collection scales
    with devices like the train chunk, with no collective.
    ``n_traces`` counts retraces — one compile per distinct ``(A, B)``
    bucket (cohorts pad the agent axis to pow2 buckets).
    """

    def __init__(self, cfg: DQNConfig, mesh=None):
        self.cfg = cfg
        self.mesh = mesh
        self.n_traces = 0

        def qvals(stacked, obs, loc):
            self.n_traces += 1  # trace-time side effect: counts retraces
            return jax.vmap(lambda p, o, l: dqn_apply(cfg, p, o, l))(
                stacked, obs, loc
            )

        if mesh is None:
            self.qvals: Callable = jax.jit(qvals)
        else:
            # one shard_map program, like the train chunk: each device
            # acts for its own agents, with no collective
            fs = FleetSharding(mesh)
            spec = fs.stacked.spec
            self.qvals = jax.jit(
                jax.shard_map(
                    qvals, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec
                ),
                in_shardings=(fs.stacked, fs.stacked, fs.stacked),
                out_shardings=fs.stacked,
            )


_COLLECT_STEPS_CACHE: dict[tuple, CollectSteps] = {}


def make_collect_steps(cfg: DQNConfig, *, mesh=None) -> CollectSteps:
    """(config, mesh)-keyed cache of the stacked collection program."""
    key = (cfg, mesh)
    steps = _COLLECT_STEPS_CACHE.get(key)
    if steps is None:
        steps = CollectSteps(cfg, mesh)
        _COLLECT_STEPS_CACHE[key] = steps
    return steps


def collect_fleet(agents, envs, erbs, n_episodes: int) -> None:
    """Collect one round of experience for a cohort of fleet agents with
    the stacked act program — one vmapped q-value dispatch per
    environment step for the whole cohort.

    ``agents[i]`` rolls ``n_episodes`` episodes in ``envs[i]``, appending
    transitions to ``erbs[i]``. Bit-identical to calling
    ``DQNAgent.collect`` per agent: each vmap lane runs the agent's own
    slot program on its own batch (bitwise-equal q-values), and every
    epsilon-greedy draw (`start_locs`, action integers, exploration
    coins) comes from that agent's own ``np.random.Generator`` in the
    identical order. Agents whose episodes all finish early stop
    consuming their rng and stop writing their ERB, exactly like the
    per-agent loop's early ``break``.

    Each location is cropped once: the crops of a step's new locations
    are both that step's ``next_obs`` and the next dispatch's staged
    observations (the per-agent loop crops them twice). The
    ``fleet.collect`` span counts the rows cropped as ``crop_rows``.
    """
    if not agents:
        return
    engine = agents[0].engine
    tel = engine.telemetry
    cfg = agents[0].cfg
    steps = make_collect_steps(cfg, mesh=engine.mesh)
    n = len(agents)
    n_min = engine.mesh.size if engine.mesh is not None else 1
    a_pad = max(_pow2(n), n_min)
    slots = [a.slot for a in agents] + [agents[0].slot] * (a_pad - n)
    # may flush the cohort's pending jobs: outside the collect span, so
    # fleet.flush and fleet.collect never nest
    stacked = engine.padded_slot_params(slots)
    box = cfg.box_size
    b = n_episodes
    with tel.wall_span("fleet.collect", "fleet", agents=n) as span:
        locs = np.stack([env.start_locs(b, a.rng) for a, env in zip(agents, envs)])
        alive = np.ones((n, b), bool)
        obs_buf = np.zeros((a_pad, b, *box), np.float32)
        loc_buf = np.zeros((a_pad, b, 3), np.float32)
        for i in range(n):
            envs[i].observe(locs[i], out=obs_buf[i])
        crop_rows = n * b
        dispatches = env_steps = 0
        last = cfg.max_episode_steps - 1
        for t in range(cfg.max_episode_steps):
            live = [i for i in range(n) if alive[i].any()]
            if not live:
                break
            for i in live:
                loc_buf[i] = envs[i].norm_loc(locs[i])
            q = np.asarray(
                steps.qvals(stacked, jnp.asarray(obs_buf), jnp.asarray(loc_buf))
            )
            dispatches += 1
            for i in live:
                agent, env, erb = agents[i], envs[i], erbs[i]
                eps = agent.epsilon()
                greedy = q[i].argmax(-1)
                rand = agent.rng.integers(0, cfg.n_actions, size=b)
                coin = agent.rng.random(b) < eps
                acts = np.where(coin, rand, greedy).astype(np.int32)
                new, r, done = env.step(locs[i], acts)
                idx = np.where(alive[i])[0]
                # obs_buf[i] holds this step's crops at locs[i]: copy the
                # live rows out before the next crops overwrite them
                obs = obs_buf[i][idx]
                alive[i] &= ~done
                if t < last and alive[i].any():
                    # the next dispatch stages every row at new (dead rows
                    # keep moving): one crop is also this step's next_obs
                    env.observe(new, out=obs_buf[i])
                    next_obs = obs_buf[i] if len(idx) == b else obs_buf[i][idx]
                    crop_rows += b
                else:
                    next_obs = env.observe(new[idx])
                    crop_rows += len(idx)
                batch = {
                    "obs": obs,
                    "loc": loc_buf[i][idx],
                    "action": acts[idx],
                    "reward": r[idx],
                    "next_obs": next_obs,
                    "next_loc": env.norm_loc(new[idx]),
                    "done": done[idx].astype(np.float32),
                }
                erb_add(erb, batch)
                env_steps += len(idx)
                locs[i] = new
        if tel.enabled:
            span.update(
                dispatches=dispatches,
                env_steps=env_steps,
                crop_rows=crop_rows,
                h2d_bytes=dispatches * (obs_buf.nbytes + loc_buf.nbytes),
            )


class ActSteps:
    """The compiled batched greedy-act program of one config.

    ``act(stacked, slot, obs, loc) -> (actions, q)`` where ``stacked``
    is a parameter pytree with one leading stacked axis (fleet slots, or
    the serving plane's flattened version x agent grid), ``slot`` is the
    per-request [B] int32 row into that axis, and ``obs``/``loc`` are
    the [B, *box] / [B, 3] observation batch. Each request runs as an
    independent ``vmap`` lane gathering its own parameter rows, so the
    per-request math is bitwise invariant to the batch it shares a
    dispatch with — the same slot-independence that backs the fleet
    train chunk's N-invariance (``tests/test_fleet.py``) makes batched
    serving bit-identical to single-request serving.

    ``n_traces`` counts retraces; one compile per distinct batch-size
    bucket, so a service that pads to pow2 buckets stops retracing once
    its buckets are warm (asserted by the serve tests and surfaced by
    ``launch.serve --fleet`` as ``recompiles_after_warmup``).
    """

    def __init__(self, cfg: DQNConfig):
        self.cfg = cfg
        self.n_traces = 0

        def one(stacked, slot, obs, loc):
            p = jax.tree_util.tree_map(lambda x: x[slot], stacked)
            return dqn_apply(cfg, p, obs[None], loc[None])[0]

        def act(stacked, slot, obs, loc):
            self.n_traces += 1  # trace-time side effect: counts retraces
            q = jax.vmap(one, in_axes=(None, 0, 0, 0))(stacked, slot, obs, loc)
            return jnp.argmax(q, axis=-1).astype(jnp.int32), q

        self.act: Callable = jax.jit(act)

    def warmup(self, stacked, batch_sizes: Sequence[int]) -> None:
        """Compile every bucket entrypoint up front (zero-filled inputs;
        the results are discarded)."""
        box = self.cfg.box_size
        for b in batch_sizes:
            slot = jnp.zeros((b,), jnp.int32)
            obs = jnp.zeros((b, *box), jnp.float32)
            loc = jnp.zeros((b, 3), jnp.float32)
            jax.block_until_ready(self.act(stacked, slot, obs, loc))


_ACT_STEPS_CACHE: dict[DQNConfig, ActSteps] = {}


def make_act_steps(cfg: DQNConfig) -> ActSteps:
    """Config-keyed cache of the batched act program (one compile per
    batch bucket shared by every service/evaluator of this config)."""
    steps = _ACT_STEPS_CACHE.get(cfg)
    if steps is None:
        steps = ActSteps(cfg)
        _ACT_STEPS_CACHE[cfg] = steps
    return steps


class TrainFuture:
    """Resolution handle of a submitted training job: ``loss`` is the
    last-step TD loss once the job's chunk has flushed."""

    __slots__ = ("done", "loss", "_cbs")

    def __init__(self):
        self.done = False
        self.loss: float | None = None
        self._cbs: list[Callable[[float], None]] = []

    def on_done(self, cb: Callable[[float], None]) -> None:
        if self.done:
            cb(self.loss)
        else:
            self._cbs.append(cb)

    def resolve(self, loss: float) -> None:
        self.done = True
        self.loss = float(loss)
        cbs, self._cbs = self._cbs, []
        for cb in cbs:
            cb(self.loss)


class _Job:
    """One pending round of training for a slot: the ERBs it reads and
    the per-step (erb-position, row) selection, shuffle already applied."""

    __slots__ = ("slot", "n_steps", "erbs", "eidx", "rows", "future")

    def __init__(self, slot, n_steps, erbs, eidx, rows, future):
        self.slot = slot
        self.n_steps = n_steps
        self.erbs: list[ERB] = erbs
        self.eidx: np.ndarray = eidx  # [K, B] int32 position into self.erbs
        self.rows: np.ndarray = rows  # [K, B] int32 local row index
        self.future: TrainFuture = future


def _pow2(n: int) -> int:
    return 1 << max(0, n - 1).bit_length()


class FleetEngine:
    """Host-side orchestrator of one stacked fleet.

    Slots are added per agent; training rounds are *submitted* as jobs
    (pure index plans — no data moves) and executed lazily: any read or
    write of a slot's state forces a flush, and a flush trains **all**
    pending jobs in one scan-fused, vmapped dispatch. Futures resolve in
    submission order, so deferred bookkeeping (round records) lands in
    the same order as sequential execution.

    The resident slot axis is padded to a pow2, mesh-divisible
    ``capacity``: rows past ``n_slots`` are *dead* — inert copies that
    are never read and get overwritten in place when a slot is added, so
    growth (and churn re-adds) no longer reshapes the stacked arrays or
    forces a flush while capacity is spare. With a ``mesh`` (a 1-D
    agent-axis device mesh from
    :func:`repro.models.sharding.make_fleet_mesh`), the resident state is
    committed to agent-axis shardings and flushes that cover the whole
    bucket pass it straight through the donated sharded chunk — no
    gather, no scatter, device-resident end to end.
    """

    def __init__(
        self,
        cfg: DQNConfig,
        *,
        mesh=None,
        use_pallas: bool = False,
        erb_cache_size: int = 128,
        erb_cache_bytes: int = 256 * 1024**2,
        pool_bucket_floor: int = 128,
    ):
        self.cfg = cfg
        self.use_pallas = bool(use_pallas)
        self.mesh = mesh
        if mesh is not None and (mesh.size & (mesh.size - 1)):
            raise ValueError("fleet mesh size must be a power of two")
        self.sharding = FleetSharding(mesh) if mesh is not None else None
        self.steps = make_fleet_steps(cfg, use_pallas=use_pallas, mesh=mesh)
        self.state: FleetState | None = None
        self.n_slots = 0
        self.capacity = 0  # resident rows (pow2, mesh-divisible; >= n_slots)
        self.erb_cache_size = erb_cache_size
        self.erb_cache_bytes = erb_cache_bytes
        self.pool_bucket_floor = pool_bucket_floor
        self._feat = pool_width(flat_width(cfg.box_size))
        self._pending: list[_Job] = []
        self._pending_slots: set = set()
        self._erb_cache: OrderedDict[tuple[str, int], jax.Array] = OrderedDict()
        self._erb_cache_nbytes = 0
        self._views: dict[int, FleetState] = {}
        # flush statistics (fleet_throughput reports these)
        self.n_flushes = 0
        self.n_steps_trained = 0
        self.flush_sizes: list[int] = []
        # observability: the owning system replaces these after
        # construction (ADFLLSystem / ServeSession) — NULL costs nothing.
        # With an observatory attached, flushes run the stats-carrying
        # chunk and drain per-agent learning dynamics at the same
        # boundary as the loss sync.
        self.telemetry = NULL
        self.sim_clock: Callable[[], float] | None = None
        self.observatory = None

    # -- slots ---------------------------------------------------------------
    def add_slot(self, seed: int) -> int:
        slot = self.n_slots
        slot_state = self.steps.init_slot(seed)
        if slot < self.capacity:
            # reuse a dead row in place: live rows are untouched, so jobs
            # already queued for other slots keep batching (no flush)
            self.state = jax.tree_util.tree_map(
                lambda s, v: s.at[slot].set(v[0]), self.state, slot_state
            )
        else:
            if self.state is not None:
                self.flush()  # resident axis grows: retire pending jobs first
            n_min = self.mesh.size if self.mesh is not None else 1
            new_cap = max(_pow2(slot + 1), n_min)
            # the dead tail holds copies of the fresh slot: inert rows,
            # never read, overwritten on reuse
            tiled = jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x, (new_cap - slot, *x.shape[1:])),
                slot_state,
            )
            if self.state is None:
                self.state = tiled
            else:
                self.state = jax.tree_util.tree_map(
                    lambda s, t: jnp.concatenate([s, t], axis=0), self.state, tiled
                )
            self.capacity = new_cap
        if self.sharding is not None:
            self.state = self.sharding.place(self.state)
        self._views.pop(slot, None)
        self.n_slots = slot + 1
        return slot

    # -- state access (flush-on-read/write) -----------------------------------
    def ensure_flushed(self, slot: int | None = None) -> None:
        """Flush all pending jobs iff ``slot`` has one (or any, if None)."""
        if slot is None:
            if self._pending:
                self.flush()
        elif slot in self._pending_slots:
            self.flush()

    def _view(self, slot: int) -> FleetState:
        v = self._views.get(slot)
        if v is None:
            v = jax.tree_util.tree_map(lambda x: x[slot], self.state)
            self._views[slot] = v
        return v

    def get_params(self, slot: int):
        self.ensure_flushed(slot)
        return self._view(slot).params

    def stacked_params(self):
        """Flush-on-read snapshot of *every live* slot's params as one
        stacked [N, ...] pytree — the serving plane's publish path
        (:class:`repro.serve.ParamPublisher` reads this between ticks).
        Dead padding rows never leak: the slice stops at ``n_slots``."""
        self.ensure_flushed()
        if self.n_slots == self.capacity:
            return self.state.params
        return jax.tree_util.tree_map(lambda x: x[: self.n_slots], self.state.params)

    def padded_slot_params(self, slots: Sequence[int]):
        """Stacked params of ``slots`` (repeats allowed — collection pads
        cohorts with duplicates of the first slot), flushing only the
        touched slots' pending work, same laziness as ``get_params``.
        When the cohort covers the whole resident bucket in order, the
        resident (already mesh-committed) arrays are returned as-is."""
        for s in set(slots):
            self.ensure_flushed(s)
        if list(slots) == list(range(self.capacity)):
            return self.state.params
        g = jnp.asarray(np.asarray(slots, np.int32))
        gathered = jax.tree_util.tree_map(
            lambda x: jnp.take(x, g, axis=0), self.state.params
        )
        # the gather commits its output replicated; re-place so the stacked
        # tree matches the collect program's explicit in_shardings
        return self.sharding.place(gathered) if self.sharding else gathered

    def get_target(self, slot: int):
        self.ensure_flushed(slot)
        return self._view(slot).target

    def get_opt(self, slot: int):
        self.ensure_flushed(slot)
        return self._view(slot).opt

    def _set_field(self, slot: int, field: str, value) -> None:
        self.ensure_flushed(slot)
        updated = jax.tree_util.tree_map(
            lambda s, v: s.at[slot].set(jnp.asarray(v)),
            getattr(self.state, field),
            value,
        )
        parts = {
            f: getattr(self.state, f)
            for f in ("params", "target", "opt", "rng", "count")
        }
        parts[field] = updated
        self.state = FleetState(**parts)
        if self.sharding is not None:
            self.state = self.sharding.place(self.state)
        self._views.pop(slot, None)

    def set_params(self, slot: int, params) -> None:
        self._set_field(slot, "params", params)

    def set_target(self, slot: int, target) -> None:
        self._set_field(slot, "target", target)

    def set_opt(self, slot: int, opt) -> None:
        self._set_field(slot, "opt", opt)

    # -- replay pool ----------------------------------------------------------
    def _flat_erb(self, erb: ERB) -> tuple[jax.Array, bool]:
        """Device-resident ``[size, 1, pool_width(F)]`` rows of an ERB (the
        gather kernel's row view, zero lanes on the right; LRU-cached; keyed
        by (erb_id, version) so host-side ring appends invalidate; bounded
        by entry count *and* total bytes — at paper-scale buffers the byte
        budget binds first), and whether they came from the cache (False:
        they were uploaded now)."""
        key = (erb.meta.erb_id, erb.version)
        hit = self._erb_cache.get(key)
        if hit is not None:
            self._erb_cache.move_to_end(key)
            return hit, True
        flat = jnp.asarray(erb_flatten(erb, self._feat)[:, None, :])
        self._erb_cache[key] = flat
        self._erb_cache_nbytes += flat.nbytes
        while len(self._erb_cache) > 1 and (
            len(self._erb_cache) > self.erb_cache_size
            or self._erb_cache_nbytes > self.erb_cache_bytes
        ):
            _, evicted = self._erb_cache.popitem(last=False)
            self._erb_cache_nbytes -= evicted.nbytes
        return flat, False

    # -- job queue ------------------------------------------------------------
    def submit(self, slot: int, plans: Sequence) -> TrainFuture:
        """Queue one job: K minibatch :class:`~repro.core.replay.ReplayPlan`s
        for ``slot``. Returns a future resolving to the last-step loss."""
        if slot in self._pending_slots:
            self.flush()  # one in-flight round per slot
        future = TrainFuture()
        n_steps = len(plans)
        if n_steps == 0:
            future.resolve(0.0)
            return future
        batch = plans[0].batch_size
        erbs: list[ERB] = []
        positions: dict[str, int] = {}
        eidx = np.empty((n_steps, batch), np.int32)
        rows = np.empty((n_steps, batch), np.int32)
        for k, plan in enumerate(plans):
            e_parts, r_parts = [], []
            for erb, ridx in plan.picks:
                pos = positions.get(erb.meta.erb_id)
                if pos is None:
                    pos = len(erbs)
                    positions[erb.meta.erb_id] = pos
                    erbs.append(erb)
                e_parts.append(np.full(len(ridx), pos, np.int32))
                r_parts.append(np.asarray(ridx, np.int32))
            # permuting indices before the gather == permuting rows after
            eidx[k] = np.concatenate(e_parts)[plan.perm]
            rows[k] = np.concatenate(r_parts)[plan.perm]
        self._pending.append(_Job(slot, n_steps, erbs, eidx, rows, future))
        self._pending_slots.add(slot)
        return future

    def flush(self) -> None:
        """Train every pending job in one dispatch (per distinct K)."""
        if not self._pending:
            return
        jobs, self._pending = self._pending, []
        self._pending_slots = set()
        # chunk consecutive jobs of equal K so futures resolve in
        # submission order (one K per ADFLL run; mixed only in tests)
        i = 0
        while i < len(jobs):
            j = i + 1
            while j < len(jobs) and jobs[j].n_steps == jobs[i].n_steps:
                j += 1
            self._flush_group(jobs[i:j])
            i = j

    def _flush_group(self, jobs: list[_Job]) -> None:
        tel = self.telemetry
        with tel.wall_span("fleet.flush", "fleet") as span:
            traces0 = self.steps.n_traces
            n_real = len(jobs)
            k_steps = jobs[0].n_steps
            batch = jobs[0].eidx.shape[1]
            with tel.wall_span("fleet.flush.pool", "fleet") as pool_span:
                # one shared device pool: the union of every job's ERBs
                offsets: dict[str, int] = {}
                parts: list[jax.Array] = []
                total = n_cached = uploaded = 0
                for job in jobs:
                    for erb in job.erbs:
                        if erb.meta.erb_id not in offsets:
                            offsets[erb.meta.erb_id] = total
                            total += erb.size
                            flat, cached = self._flat_erb(erb)
                            parts.append(flat)
                            n_cached += cached
                            uploaded += 0 if cached else flat.nbytes
                # bucket pool rows and job count (powers of two,
                # mesh-divisible) to bound the number of compiled (K, N, R)
                # shape variants
                r_pad = max(self.pool_bucket_floor, _pow2(total))
                if r_pad > total:
                    parts.append(jnp.zeros((r_pad - total, 1, self._feat), jnp.float32))
                pool = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)
                if tel.enabled:
                    pool_span.update(
                        erbs=len(offsets), erbs_cached=n_cached, h2d_bytes=uploaded
                    )
            n_min = self.mesh.size if self.mesh is not None else 1
            n_pad = max(_pow2(n_real), n_min)
            slots = [job.slot for job in jobs]
            # whole-bucket fast path: the jobs cover every live slot in
            # order, so the resident state IS the chunk operand — no gather
            # in, no scatter out, and the donated buffers flow through the
            # flush end to end (padding lanes train on pool row 0; those
            # rows are dead slots, never read, overwritten on slot reuse)
            resident = (
                n_real == self.n_slots
                and slots == list(range(n_real))
                and n_pad == self.capacity
            )
            obs = self.observatory
            stats = None
            with tel.wall_span("fleet.flush.dispatch", "fleet"):
                idx = np.zeros((k_steps, n_pad, batch), np.int32)
                for jpos, job in enumerate(jobs):
                    base = np.array(
                        [offsets[e.meta.erb_id] for e in job.erbs], np.int32
                    )
                    idx[:, jpos, :] = base[job.eidx] + job.rows
                if resident:
                    sub = self.state
                else:
                    padded = slots + [slots[0]] * (n_pad - n_real)  # inert duplicates
                    gather = jnp.asarray(padded)
                    sub = jax.tree_util.tree_map(
                        lambda x: jnp.take(x, gather, axis=0), self.state
                    )
                    if self.sharding is not None:
                        # the gather commits its output replicated; re-place
                        # so the operand matches the chunk's in_shardings
                        sub = self.sharding.place(sub)
                if obs is None:
                    new, losses = self.steps.train_chunk(sub, pool, jnp.asarray(idx))
                else:
                    new, stats = self.steps.train_chunk_stats(
                        sub, pool, jnp.asarray(idx)
                    )
                    losses = stats["loss"]
                if resident:
                    self.state = new
                else:
                    real = jnp.asarray(slots)
                    self.state = jax.tree_util.tree_map(
                        lambda s, ns: s.at[real].set(ns[:n_real]), self.state, new
                    )
                    if self.sharding is not None:
                        self.state = self.sharding.place(self.state)
                self._views.clear()
            with tel.wall_span("fleet.flush.wait", "fleet"):
                losses_np = np.asarray(losses)  # the flush's one host sync
            if obs is not None and stats is not None:
                # drained at the same boundary — no extra mid-scan syncs,
                # just more values riding the flush's host transfer
                stats_np = {k: np.asarray(v) for k, v in stats.items()}
                sim_t = self.sim_clock() if self.sim_clock is not None else 0.0
                obs.on_flush(slots, stats_np, n_real, sim_t)
            self.n_flushes += 1
            self.n_steps_trained += n_real * k_steps
            self.flush_sizes.append(n_real)
            compiled = self.steps.n_traces - traces0
            if tel.enabled:
                span.update(
                    jobs=n_real,
                    k_steps=k_steps,
                    batch=batch,
                    pool_rows=int(r_pad),
                    devices=n_min,
                    resident=resident,
                    compiled=compiled,
                )
        if tel.enabled:
            if compiled:
                tel.instant("fleet.compile", "fleet", tel.wall(), clock="wall")
                tel.count("fleet.compiles", compiled)
            if self.sim_clock is not None:
                # the same flush pinned to simulated time, so trace views
                # can correlate host cost with scheduler progress
                tel.instant("fleet.flush", "fleet", self.sim_clock(), jobs=n_real)
            tel.count("fleet.flushes", 1)
            tel.count("fleet.steps_trained", n_real * k_steps)
        for jpos, job in enumerate(jobs):
            job.future.resolve(float(losses_np[-1, jpos]))


__all__ = [
    "ActSteps",
    "CollectSteps",
    "FleetEngine",
    "FleetState",
    "FleetSteps",
    "TrainFuture",
    "collect_fleet",
    "make_act_steps",
    "make_collect_steps",
    "make_fleet_steps",
]
