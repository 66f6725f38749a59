"""The device's idle time put down to the program's own spans, from one
traced window of a cell.

Every ``Tracer.wall_span`` of the program is also a
``jax.profiler.TraceAnnotation``, so the profiler's trace holds the
fleet's spans as host events on the clock of the device's operations.
This tool runs a cell's window with telemetry on under the profiler and
prints one JSON line: the first device's idle seconds inside each span
name's intervals (and their share of those intervals), the idle outside
every program span, and the ten longest idle gaps, each with the
innermost host event and the program span that cover it most.

    python3 bench/tools/idle_by_span.py --workload <cell> --seed <n> --seconds <s>
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(BENCH.parent / ".jax_cache")
sys.path.insert(0, str(BENCH))

import jax  # noqa: E402

from harness import device as dev  # noqa: E402
from harness import trace as tr  # noqa: E402
from harness.loading import Catalog  # noqa: E402
from harness.program import import_path  # noqa: E402

jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

PROGRAM_SPANS = (
    "fleet.flush",
    "fleet.flush.pool",
    "fleet.flush.dispatch",
    "fleet.flush.wait",
    "fleet.collect",
)


def merged(intervals) -> list[tuple[float, float]]:
    """Sorted, disjoint ``(start, end)`` intervals covering the same time."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def overlap_ns(a, b) -> float:
    """Length of the intersection of two merged interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0.0, e - s)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _cover(events, s: float, e: float) -> str | None:
    """Of ``events``, the one that covers most of ``[s, e]``; of those that
    cover it alike, the innermost (as ``trace.breakdown`` labels gaps)."""
    best = None
    for ev in events:
        ov = min(e, ev.end_ns) - max(s, ev.start_ns)
        if ov > 0:
            key = (round(ov / max(e - s, 1.0), 2), -ev.dur_ns, ev.name)
            best = key if best is None or key > best else best
    return None if best is None else best[2]


def idle_by_span(events: list[tr.Event], names=PROGRAM_SPANS) -> dict:
    """Idle seconds of the first device inside each of ``names``' host
    intervals and outside all of them, over its first to last operation."""
    ops = {}
    for ev in events:
        if tr._DEVICE.match(ev.plane) and ev.line == tr.OPS_LINE:
            ops.setdefault(ev.plane, []).append((ev.start_ns, ev.end_ns))
    if not ops:
        return {}
    lo = min(s for iv in ops.values() for s, _ in iv)
    hi = max(e for iv in ops.values() for _, e in iv)
    idle = merged(tr.gaps(ops[sorted(ops)[0]], lo, hi))
    idle_ns = sum(e - s for s, e in idle)
    host = [ev for ev in events if not tr._DEVICE.match(ev.plane) and ev.dur_ns > 0]
    spans = [ev for ev in host if ev.name in names]
    others = [ev for ev in host if ev.name not in names]
    out = {"window_s": (hi - lo) * 1e-9, "idle_s": idle_ns * 1e-9, "spans": {}}
    for name in names:
        iv = merged((ev.start_ns, ev.end_ns) for ev in spans if ev.name == name)
        length = sum(e - s for s, e in iv)
        inside = overlap_ns(idle, iv)
        out["spans"][name] = {
            "count": sum(ev.name == name for ev in spans),
            "s": length * 1e-9,
            "idle_s": inside * 1e-9,
            "idle_share": inside / length * 100 if length else None,
        }
    traced = overlap_ns(idle, merged((ev.start_ns, ev.end_ns) for ev in spans))
    out["untraced_idle_s"] = (idle_ns - traced) * 1e-9
    out["untraced_idle_share"] = (idle_ns - traced) / idle_ns * 100 if idle_ns else None
    out["gaps"] = [
        {
            "s": (e - s) * 1e-9,
            "at_s": (s - lo) * 1e-9,
            "host": _cover(others, s, e) or "host",
            "span": _cover(spans, s, e) or "untraced",
        }
        for s, e in sorted(idle, key=lambda g: g[0] - g[1])[:10]
    ]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument(
        "--cpu",
        action="store_true",
        help="rehearsal on the CPU: its XLA threads stand in for a device",
    )
    ap.add_argument("--root", help="a directory of cells with its own BENCHMARK.json")
    args = ap.parse_args(argv)
    root = Path(args.root) if args.root else None
    cat = Catalog(root, root / "BENCHMARK.json") if root else Catalog()
    w = cat.workload(args.workload)
    if not args.cpu:
        dev.require_chips(w["chips"])
    import_path()
    from repro.telemetry import Telemetry

    cfg, mix = cat.config(w["config"]), cat.traffic(w["traffic"])
    driver = cat.driver(mix["driver"])
    ref = cat.reference(cfg["reference"])
    tel = Telemetry(enabled=True)
    session = driver.Session(cfg, mix, args.seed, ref, telemetry=tel)
    with tempfile.TemporaryDirectory(prefix="idle_by_span_") as tmp:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(tmp, profiler_options=opts)
        result = session.window(args.seconds)
        jax.profiler.stop_trace()
        events = tr.read_events(tmp)
    if args.cpu:
        events = [
            tr.Event("/device:CPU:0", tr.OPS_LINE, e.name, e.start_ns, e.dur_ns)
            if e.line.startswith("tf_XLA") and e.dur_ns > 0 else e
            for e in events
        ]
    print(json.dumps(dict(idle_by_span(events), counts=result["counts"])), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
