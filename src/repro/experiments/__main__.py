"""CLI for the scenario registry.

    PYTHONPATH=src python -m repro.experiments --list
    PYTHONPATH=src python -m repro.experiments --scenario paper_fig2 [--fast]
    PYTHONPATH=src python -m repro.experiments \
        --scenario churn_addition_fig4 --scenario gossip_hetero \
        --fast --json BENCH_experiments.json

``--json`` writes the ``check_regression``-compatible shape (one
``configs`` entry per scenario), so CI can gate scenario runs exactly
like the classic benchmarks.
"""

from __future__ import annotations

import argparse
import sys
from dataclasses import replace

from repro.experiments.registry import get_scenario, list_scenarios
from repro.experiments.runner import resolve, run, write_json
from repro.experiments.suggest import unknown_name_message


def _per_scenario(path: str | None, name: str, n_scenarios: int) -> str | None:
    """Insert the scenario name before the extension for multi-scenario runs."""
    if path is None or n_scenarios <= 1:
        return path
    stem, dot, ext = path.rpartition(".")
    return f"{stem}.{name}.{ext}" if dot else f"{path}.{name}"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m repro.experiments")
    ap.add_argument(
        "--list", action="store_true", help="list registered scenarios and exit"
    )
    ap.add_argument(
        "--scenario",
        action="append",
        default=[],
        metavar="NAME",
        help="scenario to run (repeatable)",
    )
    ap.add_argument(
        "--fast", action="store_true", help="reduced step counts (CI sanity)"
    )
    ap.add_argument(
        "--seed", type=int, default=None, help="override the scenario seed"
    )
    ap.add_argument(
        "--engine",
        choices=("fleet", "fleet-eager", "stepwise"),
        default=None,
        help="override the ADFLL execution engine (default: the scenario's)",
    )
    ap.add_argument(
        "--devices",
        type=int,
        default=None,
        metavar="N",
        help=(
            "shard the fleet's agent axis across a device mesh of N local "
            "devices (a power of two; -1 = all, rounded down to one). "
            "On CPU per-slot math is bitwise invariant to the mesh, so "
            "reports match single-device runs; combine with "
            "XLA_FLAGS=--xla_force_host_platform_device_count=N."
        ),
    )
    ap.add_argument(
        "--mesh",
        action="store_true",
        help="shorthand for --devices -1 (every local device)",
    )
    ap.add_argument(
        "--json",
        type=str,
        default=None,
        metavar="OUT",
        help="write results as JSON (BENCH_*.json for CI gating)",
    )
    ap.add_argument(
        "--trace",
        type=str,
        default=None,
        metavar="PATH",
        help=(
            "capture a telemetry trace per scenario (Perfetto JSON; .jsonl "
            "for the flat format). With several --scenario flags the "
            "scenario name is inserted before the extension."
        ),
    )
    ap.add_argument(
        "--dashboard",
        type=str,
        default=None,
        metavar="PATH",
        help=(
            "render the run's observatory dashboard (self-contained HTML) "
            "per scenario; multi-scenario name insertion as for --trace"
        ),
    )
    args = ap.parse_args(argv)

    if args.list or not args.scenario:
        print(f"{'scenario':<24} {'system':<12} description")
        for spec in list_scenarios():
            print(f"{spec.name:<24} {spec.system:<12} {spec.description}")
        return 0

    known = [s.name for s in list_scenarios()]
    for name in args.scenario:
        try:
            get_scenario(name)
        except KeyError:
            print(unknown_name_message("scenario", name, known), file=sys.stderr)
            return 2

    reports = []
    for name in args.scenario:
        spec = resolve(name, fast=args.fast, seed=args.seed)
        if args.engine is not None:
            spec = replace(spec, sys=replace(spec.sys, engine=args.engine))
        devices = -1 if args.mesh and args.devices is None else args.devices
        if devices is not None:
            spec = replace(spec, sys=replace(spec.sys, fleet_devices=devices))
        trace_path = _per_scenario(args.trace, name, len(args.scenario))
        dashboard_path = _per_scenario(args.dashboard, name, len(args.scenario))
        report = run(spec, trace_path=trace_path, dashboard_path=dashboard_path)
        reports.append(report)
        if trace_path is not None:
            print(f"wrote trace {trace_path}")
        if dashboard_path is not None:
            print(f"wrote dashboard {dashboard_path}")
        curve = " -> ".join(
            f"{p.mean_err:.2f}@{p.t:.1f}(n={p.n_agents})" for p in report.eval_curve
        )
        print(
            f"{report.scenario},mean_dist_err={report.mean_dist_err:.3f},"
            f"best_agent_err={report.best_agent_err:.3f},"
            f"sim_makespan={report.makespan:.2f},n_rounds={report.n_rounds},"
            f"total_bytes={report.total_bytes}"
        )
        print(f"derived,{report.scenario},eval_curve={curve}")
    if args.json:
        write_json(args.json, reports, fast=args.fast)
        print(f"wrote {args.json}")
    return 0


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
