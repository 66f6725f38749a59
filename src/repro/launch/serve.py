"""Serving CLI: the localization inference plane, plus the legacy
transformer prefill+decode driver.

Fleet mode — continuous-batching localization serving with train-while-
serve hot swaps (the production direction; see ``repro.serve``):

    PYTHONPATH=src python -m repro.launch.serve --fleet \
        [--agents 2] [--requests 64] [--max-batch 8] [--waves 2] \
        [--rate REQ_PER_S] [--seed 0] [--json OUT]

Transformer mode — one-shot prefill then batched greedy decode of a
model-zoo config (the original driver; all old flags keep working):

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-14b-smoke \
        --batch 4 --prompt-len 64 --gen 32
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_config
from repro.models.model import (
    init_caches,
    init_params,
    make_prefill_step,
    make_serve_step,
)
from repro.models.sharding import ShardingPolicy


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m repro.launch.serve")
    mode = ap.add_argument_group("mode (exactly one)")
    mode.add_argument(
        "--fleet",
        action="store_true",
        help="serve the localization fleet under synthetic traffic",
    )
    mode.add_argument("--arch", default=None, help="transformer config to decode")
    ap.add_argument("--seed", type=int, default=0)
    # -- transformer-mode flags (unchanged) --------------------------------
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    # -- fleet-mode flags --------------------------------------------------
    ap.add_argument("--agents", type=int, default=2, help="fleet slots served")
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument(
        "--waves",
        type=int,
        default=2,
        help="traffic waves; each later wave follows a train+publish "
        "round, exercising a param hot-swap",
    )
    ap.add_argument(
        "--rate",
        type=float,
        default=None,
        help="open-loop arrival rate (req/s); default: all at once",
    )
    ap.add_argument("--train-steps", type=int, default=20)
    ap.add_argument("--version-slots", type=int, default=2)
    ap.add_argument("--max-staleness", type=int, default=1)
    ap.add_argument("--json", default=None, metavar="OUT")
    args = ap.parse_args(argv)

    if args.fleet == (args.arch is not None):
        ap.error("exactly one of --fleet or --arch is required")
    if args.fleet:
        return _fleet_main(args)
    return _transformer_main(args)


def _fleet_main(args) -> int:
    """Thin driver over ``repro.serve``: build, serve, report."""
    from repro.configs.adfll_dqn import DQNConfig
    from repro.serve import TrafficSpec, build_session, run_session

    cfg = DQNConfig(
        volume_shape=(16, 16, 16),
        box_size=(6, 6, 6),
        conv_features=(4,),
        hidden=(32,),
        max_episode_steps=16,
        batch_size=16,
        eps_decay_steps=100,
    )
    traffic = TrafficSpec(
        n_requests=args.requests,
        max_batch=args.max_batch,
        n_version_slots=args.version_slots,
        max_staleness=args.max_staleness,
        rate=args.rate,
        seed=args.seed,
    )
    session = build_session(cfg, n_agents=args.agents, traffic=traffic, seed=args.seed)
    report = run_session(
        session, traffic, n_waves=args.waves, train_steps=args.train_steps
    )
    s = report.summary()
    print(
        f"served {s['n_requests']} requests in {s['wall_time_s']:.2f}s "
        f"({s['requests_per_sec']:.1f} req/s)"
    )
    print(
        f"latency p50={s['p50_latency_ms']:.1f}ms p99={s['p99_latency_ms']:.1f}ms "
        f"ticks/req={s['ticks_per_request']:.1f} "
        f"queue depth mean={s['mean_queue_depth']:.1f}"
    )
    print(
        f"hot swaps={s['n_swaps']} versions_served={s['versions_served']} "
        f"stall_ticks={s['n_stall_ticks']}"
    )
    print(
        f"compiled buckets={session.service.buckets} "
        f"recompiles_after_warmup={s['recompiles']}"
    )
    if s["mean_dist_err"] is not None:
        print(f"mean_dist_err={s['mean_dist_err']:.2f} voxels (synthetic landmarks)")
    if args.json:
        payload = {"benchmark": "serve", "fast": False, "configs": {"fleet": s}}
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
        print(f"wrote {args.json}")
    return 0 if s["recompiles"] == 0 else 1


def _transformer_main(args) -> int:
    cfg = get_config(args.arch)
    policy = ShardingPolicy()
    params = init_params(cfg, jax.random.PRNGKey(args.seed))
    prefill = jax.jit(make_prefill_step(cfg, policy=policy))
    serve = jax.jit(make_serve_step(cfg, policy=policy), donate_argnums=1)

    rng = np.random.default_rng(args.seed)
    b, s = args.batch, args.prompt_len
    total = s + args.gen
    if cfg.input_kind == "embeds":
        emb = rng.standard_normal((b, s, cfg.d_model)).astype(np.float32)
        batch = {"embeds": jnp.asarray(emb)}
    else:
        tok0 = rng.integers(0, cfg.vocab_size, (b, s))
        batch = {"tokens": jnp.asarray(tok0, jnp.int32)}

    t0 = time.time()
    last_logits, pre_caches = prefill(params, batch)
    dt = time.time() - t0
    print(f"prefill [{b}x{s}] in {dt:.2f}s")

    # decode caches sized for the full conversation; copy prefill k/v in.
    caches = init_caches(cfg, b, total)
    caches = _load_prefill(cfg, caches, pre_caches, s)

    tok = jnp.argmax(last_logits, -1).astype(jnp.int32)[:, None]
    out_tokens = [np.asarray(tok)]
    t0 = time.time()
    for i in range(args.gen):
        step_batch = {"pos": jnp.full((b,), s + i, jnp.int32)}
        if cfg.input_kind == "embeds":
            step_batch["embeds"] = jnp.zeros((b, 1, cfg.d_model), jnp.float32)
        else:
            step_batch["tokens"] = tok
        logits, caches = serve(params, caches, step_batch)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        out_tokens.append(np.asarray(tok))
    dt = time.time() - t0
    tok_s = args.gen * b / dt
    print(f"decoded {args.gen} tokens x {b} reqs in {dt:.2f}s ({tok_s:.1f} tok/s)")
    print("sample token ids:", np.concatenate(out_tokens, 1)[0][:16])
    return 0


def _load_prefill(cfg, caches, pre_caches, s):
    """Copy prefill k/v (and recurrent states) into the decode caches.

    Every prefill leaf must either match its decode leaf exactly or be a
    same-rank prefix of it (kv caches sized for the full conversation);
    anything else is a wiring bug, and silently keeping the zero decode
    cache would serve garbage — raise instead.
    """

    def copy_leaf(dst, src):
        if dst.shape == src.shape:
            return src.astype(dst.dtype)
        if dst.ndim == src.ndim and all(
            sd <= dd for sd, dd in zip(src.shape, dst.shape)
        ):
            # group-stacked kv: [G, B, S_cache, H, D] <- [G, B, s, H, D]
            sl = tuple(slice(0, d) for d in src.shape)
            return dst.at[sl].set(src.astype(dst.dtype))
        raise ValueError(
            f"prefill cache leaf {src.shape} does not fit decode cache "
            f"leaf {dst.shape} (rank or axis mismatch)"
        )

    return jax.tree_util.tree_map(copy_leaf, caches, pre_caches)


if __name__ == "__main__":
    import sys

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
