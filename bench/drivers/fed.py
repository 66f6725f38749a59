"""General generator of federated-training traffic: whole, fresh ADFLL
federation jobs, back to back, through ``ADFLLSystem`` as the experiment
runner builds it.

The mix file gives the deployment (agents, hubs, speeds, tasks, train
steps per round, episodes collected per round); the configuration file
gives the agent's network and sizes and the scale (ERB capacity and share
size, rounds per job, patients). Job ``j`` of a run seeded ``s`` is a
federation seeded from ``(s, j)``.

What ``correct`` compares (``check``): the window's first flush, one
scan-fused chunk of K steps over every job it holds, is captured at the
call. The reference starts from its own initialization of each lane's
agent and follows the same K minibatches of the same pool rows. Compared:
the loss of the first step (same weights, same batch), the largest gap of
the first three steps' losses, the gap between the norms of each
parameter leaf's change over the K steps (the median leaf; the worst
leaf, named, is a reading), the Q-values of the window's first
collection step, and whether every pool row the chunk read is a row of
the federation's experience buffers. With ``stand_in`` a mode of the
reference takes the program's place: ``fp8`` is the control.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from harness import compare, flops
from harness.program import REQUIRED_KEYS, dqn_config

CONTROL = "fp8"  # the reference mode that is the control (references/dqn.py)
# steps at which a stand-in's gaps are read: the first steps, either side
# of the target syncs at 50 and 100, and the chunk's end
GROWTH_STEPS = (1, 2, 3, 5, 10, 20, 30, 49, 50, 51, 75, 100, 101, 150)


def pow2(n: int) -> int:
    return 1 << max(0, n - 1).bit_length()


def job_seed(seed: int, job: int) -> int:
    """The federation seed of job ``job``; agents seed ``job_seed + id``,
    which stays inside 31 bits."""
    return int(np.random.SeedSequence([seed, job]).generate_state(1)[0]) % (2**31 - 1024)


def chunk_buckets(cfg: dict, mix: dict) -> list[tuple[int, int]]:
    """The (jobs bucket N, pool rows bucket R) shapes a flush can reach
    when every round's buffer fills to its cap (the exploring rounds of a
    fresh federation): N jobs hold more than N/2 rounds, each at least one
    full buffer and at most its own, its past rounds' and every peer's
    shares of every round."""
    cap, share, rounds = cfg["erb_capacity"], cfg["erb_share_size"], cfg["rounds"]
    n_agents = mix["n_agents"]
    job_max = cap * rounds + share * (n_agents - 1) * rounds
    out = []
    n = 1
    while n <= pow2(n_agents):
        lo = max(mix["pool_bucket_floor"], pow2((n // 2 + 1) * cap))
        hi = max(mix["pool_bucket_floor"], pow2(min(n, n_agents) * job_max))
        r = lo
        while r <= hi:
            out.append((n, r))
            r *= 2
        n *= 2
    return out


def run_recorded(system, rounds: int) -> list[list[list[tuple[str, int]]]]:
    """Run a federation job to its end; each flush as its jobs, each job
    as the (id, size) of the buffers it draws from, in order."""
    engine = system.engine
    flushes = []

    def record(jobs, run=engine._flush_group):
        flushes.append([[(e.meta.erb_id, e.size) for e in job.erbs] for job in jobs])
        run(jobs)

    engine._flush_group = record
    system.sched.run(stop=lambda: all(a.rounds_done >= rounds for a in system.agents.values()))
    engine.flush()
    return flushes


def pool_sequences(flushes) -> set[tuple[int, ...]]:
    """The buffer sizes, in order, of each pool these flushes build, and of
    the pool of each flush's leading jobs (a deadline flushes those)."""
    out = set()
    for jobs in flushes:
        for n in range(1, len(jobs) + 1):
            seen, sizes = set(), []
            for erb_id, size in (e for job in jobs[:n] for e in job):
                if erb_id not in seen:
                    seen.add(erb_id)
                    sizes.append(size)
            out.add(tuple(sizes))
    return out


class Capture:
    """What the window's first chunk and collection calls saw and gave."""

    def __init__(self):
        self.chunk = None
        self.qvals = None


class Session:
    def __init__(self, cfg: dict, mix: dict, seed: int, ref, telemetry=None, fault: str | None = None):
        from repro.rl.fleet import make_collect_steps, make_fleet_steps
        from repro.rl.synth import make_volume, paper_eight_tasks, patient_split

        self.cfg, self.mix, self.seed, self.ref = cfg, mix, seed, ref
        self.telemetry = telemetry
        self.fault = fault
        self.dqn = dqn_config(cfg)
        self.obs = tuple(ref.obs_shape(cfg))  # one observation as the network reads it
        if mix["task_set"] != "paper8":
            raise ValueError(f"unknown task set {mix['task_set']!r}")
        self.tasks = list(paper_eight_tasks())
        self.train_patients, _ = patient_split(cfg["n_patients"])
        # every anatomy a job can draw, once (rounds then only remix it)
        side = self.dqn.volume_shape[0]
        for p in self.train_patients:
            for pathology in sorted({t.pathology for t in self.tasks}):
                task = next(t for t in self.tasks if t.pathology == pathology)
                make_volume(task, p, n=side)
        self.steps = make_fleet_steps(self.dqn)
        self.collect = make_collect_steps(self.dqn)
        self.capture = Capture()
        self._copy = jax.jit(
            lambda t: jax.tree_util.tree_map(lambda x: x + jnp.zeros((), x.dtype), t)
        )
        self._warm()

    def reseed(self, seed: int) -> None:
        """Jobs from another seed, programs kept (for readings over many
        seeds in one process)."""
        self.seed = seed

    # -- set-up ---------------------------------------------------------------
    def _state_shape(self, n: int):
        one = jax.eval_shape(lambda: self.steps.init_slot(0))
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct((n, *x.shape[1:]), x.dtype), one
        )

    def _warm(self) -> None:
        """Compile (or load) the programs of this cell's shapes only: the
        chunk at every reachable (K, N, R) bucket, the collection step,
        and the capture's copy. The pool's row width is the one the
        federation's engine computes for this configuration, and has to be
        the reference's row lane-padded; the observation is the
        reference's ``obs_shape``, and the collection step has to take it.
        Either disagreement stops set-up with a ``ValueError``."""
        k, b = self.mix["train_steps_per_round"], self.dqn.batch_size
        f32 = jnp.float32
        name, obs = self.cfg.get("name", "?"), self.obs
        system = self._system(job_seed(self.seed, 0))
        self.width = system.engine._feat
        floats = self.ref.row_floats(self.cfg)
        if self.width != flops.lane_pad(floats):
            raise ValueError(
                f"configuration {name!r}: the program's pool row is {self.width} floats wide, "
                f"its reference's row of {floats} floats lane-padded is {flops.lane_pad(floats)}"
            )
        e = self.mix["collect_episodes"]
        try:
            collect = self.collect.qvals.lower(
                self._state_shape(1).params,
                jax.ShapeDtypeStruct((1, e, *obs), f32),
                jax.ShapeDtypeStruct((1, e, 3), f32),
            )
        except TypeError as err:
            raise ValueError(
                f"configuration {name!r}: the program's collection step takes no observation of "
                f"its reference's obs_shape {obs}: {err}"
            ) from err
        collect.compile()
        self.buckets = chunk_buckets(self.cfg, self.mix)
        for n, r in self.buckets:
            state = self._state_shape(n)
            pool = jax.ShapeDtypeStruct((r, 1, self.width), f32)
            idx = jax.ShapeDtypeStruct((k, n, b), jnp.int32)
            self.steps.train_chunk.lower(state, pool, idx).compile()
        for n in sorted({n for n, _ in self.buckets}):
            self._copy.lower(self._state_shape(n)).compile()
        self._warm_flushes(system)
        del system  # its engine's state and buffer cache go before the pools are built
        self._warm_pools()

    def _warm_flushes(self, system) -> None:
        """Drive one federation's engine through a flush of 1, 2, 3 and 4
        jobs, each job over its own full buffer of zeros: the per-leaf
        gathers and scatters of every jobs bucket and the collection's
        parameter gather run once here, not in the window."""
        from repro.core.erb import erb_add, erb_init

        engine, agents = system.engine, list(system.agents.values())
        cap, b = self.cfg["erb_capacity"], self.dqn.batch_size
        task = self.tasks[0]
        zeros = {k: np.zeros_like(v) for k, v in erb_init(cap, self.obs, task=task).data.items()}
        rng = np.random.default_rng(0)
        # 1, 2, 3 and 4 jobs in slot order, then 4 in another order (a
        # flush of every slot that is not the resident fast path)
        for order in [agents[:n] for n in range(1, len(agents) + 1)] + [agents[::-1]]:
            for a in order:
                erb = erb_add(erb_init(cap, self.obs, task=task), zeros)
                plans = [a.sampler.plan(rng, b, erb) for _ in range(self.mix["train_steps_per_round"])]
                engine.submit(a.slot, plans)
            engine.flush()
        jax.block_until_ready(engine.padded_slot_params([agents[0].slot]))

    def _warm_pools(self) -> None:
        """Build once every replay pool that the window's first
        ``warm_jobs`` jobs can build: a flush concatenates the buffers of
        its jobs, each new sequence of buffer sizes a program of its own,
        and pads them with zeros to a power of two of rows. A twin of each
        job (its seed, so the same schedule and buffers; crop and network
        shrunk) runs and records the buffers of each flush; a deadline that
        stops the window mid-job flushes the leading jobs of the flush that
        was due. The twin collects and trains as many steps as the window
        does: each agent's random stream, which decides which buffers a
        job draws rows from and in what order, then runs as in the window
        (with one train step a round, or shorter episodes, some sequences
        differ and compile in the window). The twin is built from the
        ``REQUIRED_KEYS`` alone: no other network key of the configuration
        reaches it, since the network moves no buffer size."""
        twin = dqn_config(dict(
            {k: self.cfg[k] for k in REQUIRED_KEYS}, box_size=[5, 5, 5], conv_features=[4], hidden=[8]
        ))
        flushes = []
        for j in range(self.mix["warm_jobs"]):
            flushes += run_recorded(self._system(job_seed(self.seed, j), twin, telemetry=False), self.cfg["rounds"])
        self.pool_sequences = sequences = pool_sequences(flushes)
        blocks = {n: jnp.zeros((n, 1, self.width), jnp.float32) for n in {n for s in sequences for n in s}}
        for sizes in sorted(sequences):
            parts = [blocks[n] for n in sizes]
            total = sum(sizes)
            rows = max(self.mix["pool_bucket_floor"], pow2(total))
            if rows > total:
                parts.append(jnp.zeros((rows - total, 1, self.width), jnp.float32))
            if len(parts) > 1:
                jax.block_until_ready(jnp.concatenate(parts, axis=0))

    def _system(self, seed: int, dqn=None, telemetry: bool = True):
        from repro.configs.adfll_dqn import ADFLLConfig
        from repro.core.federated import ADFLLSystem

        mix, cfg = self.mix, self.cfg
        sys_cfg = ADFLLConfig(
            n_agents=mix["n_agents"],
            n_hubs=mix["n_hubs"],
            agent_hub=tuple(mix["agent_hub"]),
            agent_speed=tuple(mix["agent_speed"]),
            hub_sync_period=mix["hub_sync_period"],
            rounds=cfg["rounds"],
            erb_capacity=cfg["erb_capacity"],
            erb_share_size=cfg["erb_share_size"],
            train_steps_per_round=mix["train_steps_per_round"],
            topology=mix["topology"],
            share_planes=tuple(mix["share_planes"]),
            seed=seed,
        )
        system = ADFLLSystem(sys_cfg, dqn or self.dqn, self.tasks, self.train_patients)
        if telemetry and self.telemetry is not None:
            # flush spans from the engine; no observatory, so the chunk
            # traced is the chunk every untraced run drives
            system.engine.telemetry = self.telemetry
        return system

    # -- the timed path ---------------------------------------------------------
    def _install(self) -> None:
        steps, collect, cap, copy = self.steps, self.collect, self.capture, self._copy
        chunk0, qvals0 = steps.train_chunk, collect.qvals
        fault = self.fault

        def chunk(state, pool, idx):
            idx_in = idx
            if fault == "half_batch":  # half the batch dropped, mean over the rest
                h = idx.shape[-1] // 2
                idx = jnp.concatenate([idx[..., :h], idx[..., :h]], -1)
            first = cap.chunk is None
            if first or fault == "unchanged":
                state_in = copy(state)
            new, losses = chunk0(state, pool, idx)
            if fault == "unchanged":  # a step that returns its state unchanged
                new = state_in
            if first:
                cap.chunk = {
                    "state_in": state_in,
                    "state_out": copy(new),
                    "pool": pool,
                    "idx": idx_in,
                    "losses": losses,
                }
                if fault is None:
                    steps.train_chunk = chunk0
            return new, losses

        def qvals(stacked, obs, loc):
            q = qvals0(stacked, obs, loc)
            if fault == "altered":  # an answer altered where it is produced
                q = q.at[0, 0].set(q[0, 0, ::-1])
            # the crops' host buffer is reused step to step: copy it now
            cap.qvals = {"params": stacked, "obs": np.array(obs), "loc": np.array(loc), "q": q}
            collect.qvals = qvals0
            return q

        steps.train_chunk = chunk
        collect.qvals = qvals
        self._originals = (chunk0, qvals0)

    def _uninstall(self) -> None:
        self.steps.train_chunk, self.collect.qvals = self._originals

    def window(self, seconds: float) -> dict:
        self._install()
        rounds = self.cfg["rounds"]
        steps = rounds_done = flushes = jobs = 0
        episode_steps: list[int] = []
        self.job0 = None
        t0 = time.perf_counter()
        deadline = t0 + seconds
        while True:
            seed = job_seed(self.seed, jobs)
            system = self._system(seed)
            agents = system.agents.values()
            system.sched.run(
                stop=lambda: time.perf_counter() >= deadline
                or all(a.rounds_done >= rounds for a in agents)
            )
            system.engine.flush()  # the closing flush, with its host sync
            steps += system.engine.n_steps_trained
            flushes += system.engine.n_flushes
            rounds_done += sum(a.rounds_done for a in agents)
            episode_steps += [e.version for a in agents for e in a.personal_erbs]
            if jobs == 0:
                self.job0 = {
                    "seed": seed,
                    "erbs": [e for a in agents for e in a.personal_erbs],
                }
            jobs += 1
            del system, agents
            if time.perf_counter() >= deadline:
                break
        t1 = time.perf_counter()
        self._uninstall()
        ep = np.asarray(episode_steps)
        return {
            "train_steps_per_s": steps / (t1 - t0),
            "counts": {
                "window_s": t1 - t0,
                "agent_steps": steps,
                "agent_rounds": rounds_done,
                "flushes": flushes,
                "jobs": jobs,
                "collect_steps_per_round": {
                    "n": int(ep.size),
                    "min": int(ep.min()) if ep.size else 0,
                    "mean": float(ep.mean()) if ep.size else 0.0,
                    "max": int(ep.max()) if ep.size else 0,
                    "at_step_cap": int((ep >= self.dqn.max_episode_steps).sum()),
                },
            },
            "attempted": rounds_done,
            "failed": 0,
        }

    # -- correctness ------------------------------------------------------------
    def take_capture(self) -> dict:
        """Host copies of what the check needs; drops every device array
        the program made."""
        c, q = self.capture.chunk, self.capture.qvals
        host = {
            "state_in": jax.device_get(c["state_in"].params),
            "state_out": jax.device_get(c["state_out"].params),
            "pool": np.asarray(c["pool"])[:, 0, :],
            "idx": np.asarray(c["idx"]),
            "losses": np.asarray(c["losses"]),
            "count_in": np.asarray(c["state_in"].count),
            "q_params": jax.device_get(q["params"]),
            "q_obs": np.asarray(q["obs"]),
            "q_loc": np.asarray(q["loc"]),
            "q": np.asarray(q["q"]),
            "job0": self.job0,
        }
        self.capture = Capture()
        self.job0 = None
        return host

    def free(self) -> None:
        self.steps = self.collect = None
        jax.clear_caches()


def _init_gap(prog, ref_params) -> float:
    """How far a lane's starting weights lie from the reference's
    initialization of one agent (worst leaf)."""
    return max(compare.rel_gap(a, b) for a, b in zip(compare.leaves(prog), compare.leaves(ref_params)))


def _lane(tree, i):
    return jax.tree_util.tree_map(lambda x: np.asarray(x[i]), tree)


def _change_norms(after, before) -> np.ndarray:
    return np.array([np.linalg.norm(a - b) for a, b in zip(compare.leaves(after), compare.leaves(before))])


def _reference(memo: dict, key, fn):
    if key not in memo:
        memo[key] = fn()
    return memo[key]


def check(cfg: dict, mix: dict, cap: dict, ref, seed: int, stand_in: str | None = None,
          memo: dict | None = None, detail: bool = False) -> dict:
    """The compared numbers. With ``stand_in`` (a mode of the reference,
    ``CONTROL`` for the control) the reference computed in that mode
    stands in the program's place, and the numbers also give how its
    median-leaf and worst-leaf gaps grow with the steps taken
    (``dparam_gap_by_k``, ``dparam_worst_by_k``).
    With ``detail`` (the readings tool) the numbers also hold, under
    ``detail``, readings that are not compared: each kept leaf's gap after
    the chunk and its size and first-gradient norm over the median leaf's
    (``leaves``), the gap of the change's norm over all kept leaves
    together (``global``, signed, per lane), and for a stand-in each leaf's
    gap at each step (``leaf_by_k``) and the worst and median leaf's gap of
    the first gradient's norms (``grad1_worst``, ``grad1_gap``).
    ``memo`` keeps the reference's own results between calls on one
    capture."""
    memo = {} if memo is None else memo
    seed = cap["job0"]["seed"]
    inits = _reference(memo, "inits", lambda: [
        jax.tree_util.tree_map(np.asarray, ref.init_params(seed + a, cfg)) for a in range(mix["n_agents"])
    ])
    names = compare.leaf_names(inits[0])
    sizes = [x.size for x in compare.leaves(inits[0])]
    idx, losses = cap["idx"], cap["losses"]
    real = [i for i in range(idx.shape[1]) if idx[:, i, :].any()]
    pool = jnp.asarray(cap["pool"])
    out = {"loss1_gap": 0.0, "loss_gap": 0.0, "dparam_gap": 0.0, "dparam_worst": 0.0,
           "dparam_leaf": "", "init_gap": 0.0}
    steps = [k for k in GROWTH_STEPS if k <= idx.shape[0]]
    growth = np.zeros((2, len(steps)))  # median leaf, worst leaf
    more = {"leaves": {}, "global": [], "leaf_by_k": {}, "grad1_worst": 0.0, "grad1_gap": 0.0} if detail else None
    for lane in real:
        prog_in = _lane(cap["state_in"], lane)
        gaps = [_init_gap(prog_in, p) for p in inits]
        agent = int(np.argmin(gaps))
        out["init_gap"] = max(out["init_gap"], gaps[agent])
        start = inits[agent]
        count0 = int(cap["count_in"][lane])
        ref_loss, ref_g1, ref_moved = _reference(
            memo, ("train", lane), lambda: ref.train(cfg, start, pool, idx[:, lane, :], count0)
        )
        if stand_in is None:
            prog_loss = losses[:, lane]
            prog_moved = _change_norms(_lane(cap["state_out"], lane), prog_in)
        else:
            prog_loss, si_g1, si_moved = ref.train(cfg, start, pool, idx[:, lane, :], count0, stand_in)
            prog_moved = si_moved[-1]
            for j, k in enumerate(steps):
                g, kept = compare.change_norm_gaps(si_moved[k - 1], ref_moved[k - 1], ref_g1)
                growth[:, j] = np.maximum(growth[:, j], [np.median(g), np.max(g)])
                if more:
                    for i, v in zip(kept, g):
                        row = more["leaf_by_k"].setdefault(names[i], [0.0] * len(steps))
                        row[j] = max(row[j], float(v))
            if more:
                g, _ = compare.change_norm_gaps(si_g1, ref_g1, ref_g1)
                more["grad1_worst"] = max(more["grad1_worst"], float(np.max(g)))
                more["grad1_gap"] = max(more["grad1_gap"], float(np.median(g)))
        rel = np.abs(np.asarray(prog_loss[:3], np.float64) - ref_loss[:3]) / np.abs(ref_loss[:3])
        out["loss1_gap"] = max(out["loss1_gap"], float(rel[0]))
        out["loss_gap"] = max(out["loss_gap"], float(rel.max()))
        leaf_gaps, keep = compare.change_norm_gaps(prog_moved, ref_moved[-1], ref_g1)
        out["dparam_gap"] = max(out["dparam_gap"], float(np.median(leaf_gaps)))
        worst = int(np.argmax(leaf_gaps))
        if leaf_gaps[worst] >= out["dparam_worst"]:
            out["dparam_worst"] = float(leaf_gaps[worst])
            out["dparam_leaf"] = names[keep[worst]]
        if more:
            g1_median = float(np.median(ref_g1[keep]))
            for i, v in zip(keep, leaf_gaps):
                was = more["leaves"].get(names[i], [0.0, sizes[i], np.inf])
                more["leaves"][names[i]] = [max(was[0], float(v)), sizes[i], min(was[2], float(ref_g1[i]) / g1_median)]
            dp, dr = (np.sqrt(np.sum(np.asarray(x, np.float64)[keep] ** 2)) for x in (prog_moved, ref_moved[-1]))
            more["global"].append(float((dp - dr) / dr))
    if stand_in is not None:
        out["dparam_gap_by_k"] = dict(zip(map(str, steps), growth[0].tolist()))
        out["dparam_worst_by_k"] = dict(zip(map(str, steps), growth[1].tolist()))
    if more:
        if stand_in is None:
            del more["leaf_by_k"], more["grad1_worst"], more["grad1_gap"]
        out["detail"] = dict(more, steps=steps)
    # the collection step: the lane's own agent, the program's crops
    q_in = _lane(cap["q_params"], 0)
    gaps = [_init_gap(q_in, p) for p in inits]
    agent = int(np.argmin(gaps))
    obs, loc = cap["q_obs"][0], cap["q_loc"][0]
    q_ref = _reference(memo, "q", lambda: ref.qvals(cfg, inits[agent], obs, loc))
    q_prog = cap["q"][0] if stand_in is None else ref.qvals(cfg, inits[agent], obs, loc, stand_in)
    out["collect_q_gap"] = compare.rel_gap(q_prog, q_ref)

    # every row the chunk read is a row of the federation's buffers
    fields = ref.row_fields(cfg)

    def unmatched():
        known = set()
        for erb in cap["job0"]["erbs"]:
            known |= compare.row_digests(flat_rows(erb, fields, cap["pool"].shape[1]))
        used = np.unique(idx[:, real, :])
        return float(len(compare.row_digests(cap["pool"][used]) - known))

    out["rows_unmatched"] = _reference(memo, "rows", unmatched)
    out["lanes"] = len(real)
    return out


def flat_rows(erb, fields, width: int) -> np.ndarray:
    """An experience buffer's filled rows in the pool's flat layout:
    ``fields`` (the reference's ``row_fields``: name and flat width, in
    order), then zero lanes to ``width``. A field whose buffer is not of
    that width raises a ``ValueError``."""
    s = erb.size
    out = np.zeros((s, width), np.float32)
    o = 0
    for key, w in fields:
        col = np.asarray(erb.data[key][:s], np.float32).reshape(s, -1)
        if col.shape[1] != w:
            raise ValueError(f"buffer field {key!r} is {col.shape[1]} floats wide, the row lays out {w}")
        out[:, o : o + w] = col
        o += w
    return out
