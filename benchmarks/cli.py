"""Shared benchmark CLI plumbing.

Every benchmark main had grown the same argparse block — ``--fast``,
``--seed``, ``--json OUT`` — each with its own drift (some missing
``--seed``, none wired to the regression gate).  :func:`bench_main` is
that shape once: parse the standard flags, call the benchmark's
``run()``, and — with ``--check BASELINE`` — gate the fresh results
against a checked-in ``benchmarks/baselines/BENCH_*.json`` using the
benchmark's own declared :class:`Gate` rows (the same ``compare`` the
standalone ``check_regression`` entrypoint uses, so CI can do either).

    PYTHONPATH=src python -m benchmarks.<name> [--fast] [--seed N] \
        [--json OUT | --out OUT] [--check benchmarks/baselines/BENCH_<x>.json]
"""

from __future__ import annotations

import argparse
import inspect
import json
import sys
from collections.abc import Callable, Sequence
from dataclasses import dataclass

from benchmarks.check_regression import compare
from repro.compile_cache import enable_compile_cache


@dataclass(frozen=True)
class Gate:
    """One CI-gated metric of a benchmark's ``configs`` rows."""

    metric: str
    higher_better: bool = False
    tol: float = 0.20  # max relative regression
    abs_floor: float = 0.75  # smaller absolute deltas never fail


def build_parser(
    prog: str | None = None, *, seed: bool = False
) -> argparse.ArgumentParser:
    """The standard benchmark flag set (callers may add their own)."""
    ap = argparse.ArgumentParser(prog=prog)
    ap.add_argument(
        "--fast", action="store_true", help="reduced sizes/steps (CI sanity)"
    )
    if seed:
        ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--json",
        "--out",
        dest="json",
        type=str,
        default=None,
        metavar="OUT",
        help="write results as JSON (BENCH_*.json for CI gating)",
    )
    ap.add_argument(
        "--check",
        type=str,
        default=None,
        metavar="BASELINE",
        help="gate the fresh results against a checked-in BENCH_*.json "
        "using the benchmark's declared metrics",
    )
    ap.add_argument(
        "--devices",
        type=int,
        default=0,
        metavar="N",
        help="shard the benchmark's fleet axis across a device mesh of "
        "N local devices (a power of two; -1 = all; 0 = single-device); ignored by "
        "benchmarks without a mesh mode. On CPU combine with "
        "XLA_FLAGS=--xla_force_host_platform_device_count=N",
    )
    ap.add_argument(
        "--trace",
        type=str,
        default=None,
        metavar="PATH",
        help="capture a telemetry trace of the benchmark run (Perfetto "
        "JSON; .jsonl for the flat format); ignored by benchmarks that "
        "do not support tracing",
    )
    ap.add_argument(
        "--dashboard",
        type=str,
        default=None,
        metavar="PATH",
        help="render the run's observatory dashboard (self-contained "
        "HTML); ignored by benchmarks that do not support it",
    )
    return ap


def per_config_path(path: str | None, name: str) -> str | None:
    """``out.jsonl`` + ``hub`` -> ``out.hub.jsonl`` — one artifact per
    benchmark row (mirrors the experiments CLI's multi-scenario rule)."""
    if path is None:
        return None
    stem, dot, ext = path.rpartition(".")
    return f"{stem}.{name}.{ext}" if dot else f"{path}.{name}"


def check_gates(
    baseline_path: str, current: dict, gates: Sequence[Gate]
) -> int:
    """Run every declared gate; returns a process exit code."""
    if not gates:
        print("--check given but this benchmark declares no gates", file=sys.stderr)
        return 1
    with open(baseline_path) as f:
        baseline = json.load(f)
    failures = []
    for g in gates:
        failures += compare(
            baseline,
            current,
            tol=g.tol,
            abs_floor=g.abs_floor,
            metric=g.metric,
            higher_better=g.higher_better,
        )
    for msg in failures:
        print(f"REGRESSION {msg}", file=sys.stderr)
    return 1 if failures else 0


def bench_main(
    run: Callable[..., dict],
    *,
    benchmark: str,
    seed: bool = False,
    gates: Sequence[Gate] = (),
    argv: Sequence[str] | None = None,
) -> int:
    """The whole benchmark ``__main__``: flags -> run() -> gate.

    ``run`` is the benchmark's existing entrypoint; it receives
    ``fast``/``json_path`` (and ``seed`` when enabled) and returns the
    ``configs`` dict its JSON payload carries.
    """
    args = build_parser(f"python -m benchmarks.{benchmark}", seed=seed).parse_args(
        argv
    )
    enable_compile_cache()
    kwargs = dict(fast=args.fast, json_path=args.json)
    if seed:
        kwargs["seed"] = args.seed
    params = inspect.signature(run).parameters
    if "devices" in params:
        kwargs["devices"] = args.devices
    elif args.devices:
        print(f"--devices ignored: {benchmark} has no mesh mode")
    if "trace_path" in params:
        kwargs["trace_path"] = args.trace
    elif args.trace:
        print(f"--trace ignored: {benchmark} does not capture traces")
    if "dashboard_path" in params:
        kwargs["dashboard_path"] = args.dashboard
    elif args.dashboard:
        print(f"--dashboard ignored: {benchmark} does not render dashboards")
    results = run(**kwargs)
    if args.check:
        current = {
            "benchmark": benchmark,
            "fast": bool(args.fast),
            "configs": results,
        }
        return check_gates(args.check, current, gates)
    return 0


__all__ = ["Gate", "bench_main", "build_parser", "check_gates", "per_config_path"]
