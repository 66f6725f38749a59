"""Readings for the limits of a cell's ``correct``: the program's numbers
over many seeds and, on the same captures, each mode of the reference in
the program's place (``--stand-ins``: the control, a witness at the
program's own precision, ...), and planted faults, all in one process so
that set-up is paid once. Each reading is judged against the cell's
limits by the comparison ``run.py`` uses.

    python3 bench/tools/readings.py --workload <cell> --seconds <s> \\
        --seeds 11,12,13 [--stand-ins fp8,default] [--faults half_batch] [--fault-seeds 3]

Prints one JSON line per reading.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(BENCH.parent / ".jax_cache")
sys.path.insert(0, str(BENCH))

import jax  # noqa: E402

from harness import compare, device as dev  # noqa: E402
from harness.loading import Catalog  # noqa: E402
from harness.program import import_path  # noqa: E402

jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--stand-ins", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seeds", type=int, default=3)
    ap.add_argument("--cpu", action="store_true", help="rehearsal on the CPU")
    ap.add_argument("--root", help="a directory of cells with its own BENCHMARK.json (tests/data)")
    args = ap.parse_args(argv)
    cat = Catalog(Path(args.root), Path(args.root) / "BENCHMARK.json") if args.root else Catalog()
    w = cat.workload(args.workload)
    if not args.cpu:
        dev.require_chips(w["chips"])
    import_path()
    cfg, mix = cat.config(w["config"]), cat.traffic(w["traffic"])
    ref = cat.reference(cfg["reference"])
    driver = cat.driver(mix["driver"])
    limits = cat.limits(args.workload)
    stand_ins = [m for m in args.stand_ins.split(",") if m]
    seeds = [int(s) for s in args.seeds.split(",")]
    clock = dev.CompileClock()
    t0 = time.perf_counter()
    session = driver.Session(cfg, mix, seeds[0], ref)
    print(f"set-up {time.perf_counter() - t0:.1f} s", flush=True)

    runs = [(s, None) for s in seeds]
    for f in filter(None, args.faults.split(",")):
        runs += [(s, f) for s in seeds[: args.fault_seeds]]
    for seed, fault in runs:
        session.reseed(seed)
        session.fault = fault
        snap = clock.snapshot()
        clock.shapes(True)
        tw = time.perf_counter()
        res = session.window(args.seconds)
        in_window = clock.since(snap)
        clock.shapes(False)
        in_window["programs"] = [
            f"{p['name']} {p['args'][:300]}" for p in in_window["programs"] if not p["loaded"]
        ]
        cap = session.take_capture()
        tc = time.perf_counter()
        memo = {}  # the reference's own results on this capture
        prog = driver.check(cfg, mix, cap, ref, seed, memo=memo, detail=True)
        tp = time.perf_counter() - tc
        row = {"seed": seed, "fault": fault, "window_s": tc - tw, "check_s": tp,
               "correct": compare.judge(prog, limits)[0], "program": prog, "counts": res["counts"],
               "in_window": in_window}
        if fault is None:
            for mode in stand_ins:
                tc = time.perf_counter()
                numbers = driver.check(cfg, mix, cap, ref, seed, stand_in=mode, memo=memo, detail=True)
                row[mode] = dict(numbers, correct=compare.judge(numbers, limits)[0],
                                 seconds=time.perf_counter() - tc)
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
