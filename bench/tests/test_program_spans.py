"""Readers of the program's own spans inside the fleet (collection, the
flush's pool and wait, host-to-device bytes), on hand-built runs; and the
trace reduction with the program's spans present as profiler annotations."""

import importlib.util

import pytest

from harness import trace as tr
from harness.loading import BENCH, Catalog
from harness.program import import_path

CELL = "dqn45_wide.fed_paper"
NEW = (
    "collect.s_per_round",
    "flush.pool_ms_per_step",
    "flush.wait_ms_per_step",
    "h2d.mb_per_round",
)


@pytest.fixture(scope="module")
def run():
    spec = importlib.util.spec_from_file_location("bench_run", BENCH / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def cat():
    return Catalog()


def telemetry(spans):
    import_path()
    from repro.telemetry import Telemetry

    tel = Telemetry(enabled=True)
    for name, t0, t1, args in spans:
        tel.span(name, "fleet", t0, t1, clock="wall", **args)
    return tel


FLUSH = {
    "jobs": 4,
    "k_steps": 150,
    "batch": 48,
    "pool_rows": 1024,
    "devices": 1,
    "resident": True,
    "compiled": 0,
}
POOL_SETUP = {"erbs": 4, "erbs_cached": 0, "h2d_bytes": 9_000_000}
POOL = {"erbs": 3, "erbs_cached": 1, "h2d_bytes": 50_000_000}
COLLECT1 = {"agents": 1, "dispatches": 48, "env_steps": 1152, "h2d_bytes": 400_000_000}
COLLECT2 = {"agents": 1, "dispatches": 20, "env_steps": 480, "h2d_bytes": 100_000_000}
# a set-up flush (no collection before it), then a window of two rounds'
# collection and one flush of two jobs
SPANS = [
    ("fleet.flush.pool", 0.0, 0.5, POOL_SETUP),
    ("fleet.flush.wait", 0.6, 1.4, {}),
    ("fleet.flush", 0.0, 1.5, FLUSH),
    ("fleet.collect", 10.0, 11.0, COLLECT1),
    ("fleet.collect", 11.5, 12.0, COLLECT2),
    ("fleet.flush.pool", 12.0, 12.2, POOL),
    ("fleet.flush.dispatch", 12.2, 12.3, {}),
    ("fleet.flush.wait", 12.3, 12.9, {}),
    ("fleet.flush", 12.0, 13.0, FLUSH),
]
COUNTS = {"window_s": 5.0, "agent_steps": 300, "agent_rounds": 2}


def view(run, cat, spans, devtrace=None):
    cfg = cat.config(cat.workload(CELL)["config"])
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    result = {"counts": COUNTS}
    compiles = {"programs_compiled": 0}
    return run.RunView(cfg, {}, result, telemetry(spans), devtrace, peaks, compiles)


@pytest.mark.parametrize(
    "metric,want",
    [
        ("collect.s_per_round", (1.0 + 0.5) / 2),
        ("flush.pool_ms_per_step", 0.2 / 300 * 1e3),
        ("flush.wait_ms_per_step", 0.6 / 300 * 1e3),
        ("h2d.mb_per_round", (400e6 + 100e6 + 50e6) / 2 / 1e6),
    ],
)
def test_reader_reads_the_window_spans(run, cat, metric, want):
    """The set-up flush before the first collection is left out."""
    assert cat.reader(metric).read(view(run, cat, SPANS)) == pytest.approx(want)


@pytest.mark.parametrize("metric", NEW)
def test_reader_gives_nothing_without_the_spans(run, cat, metric):
    """A program that records only ``fleet.flush`` (the parent's) reads
    nothing, and does not raise."""
    only_flush = [s for s in SPANS if s[0] == "fleet.flush"]
    assert cat.reader(metric).read(view(run, cat, only_flush)) is None


def test_new_metrics_are_declared_for_the_cell(cat):
    declared = {m["name"]: m for m in cat.per_layer(CELL)}
    for name in NEW:
        assert declared[name]["moves"] == "train_steps_per_s"
        assert (BENCH / "metrics" / f"{name}.py").is_file()


def ev(line, name, s, d, plane="/device:TPU:0"):
    return tr.Event(plane, line, name, float(s), float(d))


DEVICE = [
    ev("XLA Ops", "fusion.1 = f32[4] fusion(a)", 0, 10e9),
    ev("XLA Ops", "custom-call.3 = f32[8] custom-call(p), replay_gather", 10e9, 2e9),
    ev("XLA Ops", "fusion.1 = f32[4] fusion(b)", 20e9, 10e9),
    ev("XLA Ops", "fusion.2 = f32[4] fusion(c)", 35e9, 1e9),
    ev("XLA Modules", "jit_chunk(3)", 0, 12e9),
    ev("XLA Modules", "jit_chunk(3)", 20e9, 10e9),
    ev("python", "Linearize", 12e9, 8e9, plane="/host:CPU"),
]
# the program's spans as the profiler records their annotations: one
# around the first gap with a shorter host event inside, one alone over
# the second gap, and a child overlapping its parent
ANNOTATIONS = [
    ev("python", "fleet.flush", 11e9, 10e9, plane="/host:CPU"),
    ev("python", "fleet.flush.wait", 11.5e9, 9e9, plane="/host:CPU"),
    ev("python", "fleet.collect", 30e9, 5e9, plane="/host:CPU"),
]


def test_annotations_change_no_field_and_no_existing_reader(run, cat):
    """Adding the program's annotations to a trace leaves every field of
    the reduction and every accepted reader's value as it was; only an
    idle gap no shorter host event covered is now named by its span."""
    before = tr.reduce_events(DEVICE)
    after = tr.reduce_events(DEVICE + ANNOTATIONS)
    for f in ("n_devices", "busy_s", "span_s", "programs", "ops"):
        assert getattr(after, f) == getattr(before, f), f
    assert [s for _, s in after.idle_gaps] == [s for _, s in before.idle_gaps]
    assert dict(before.idle_gaps) == {"Linearize": pytest.approx(8.0), "host": 5.0}
    assert [label for label, _ in after.idle_gaps] == ["Linearize", "fleet.collect"]
    # every per-layer metric BENCHMARK.json declares for the cell, beside
    # the four whose spans the annotations carry
    declared = [m["name"] for m in cat.per_layer(CELL)]
    assert set(NEW) <= set(declared)
    accepted = [name for name in declared if name not in NEW]
    for name in accepted:
        reader = cat.reader(name)
        v0 = reader.read(view(run, cat, SPANS, before))
        v1 = reader.read(view(run, cat, SPANS, after))
        assert v0 == v1, name
