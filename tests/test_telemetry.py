"""Telemetry subsystem: registry semantics and cardinality bounds, the
observe-only contract (disabled = bit-identical runs, enabled = same
numbers plus a trace), Perfetto/JSONL export round-trips, and the
``python -m repro.telemetry`` CLI."""

import json

import pytest

from repro.configs.adfll_dqn import ADFLLConfig, DQNConfig
from repro.experiments import ScenarioSpec
from repro.experiments.runner import run
from repro.telemetry import (
    NULL,
    MetricsRegistry,
    Telemetry,
    load_trace,
    to_perfetto,
    write_trace,
)
from repro.telemetry.__main__ import main as tel_main

TINY_DQN = DQNConfig(
    volume_shape=(12, 12, 12),
    box_size=(4, 4, 4),
    conv_features=(2,),
    hidden=(8,),
    batch_size=4,
    max_episode_steps=4,
    eps_decay_steps=20,
)
TINY_SYS = ADFLLConfig(
    n_agents=2,
    n_hubs=1,
    agent_hub=(0, 0),
    agent_speed=(1.0, 2.0),
    rounds=2,
    erb_capacity=128,
    erb_share_size=16,
    train_steps_per_round=2,
    hub_sync_period=0.5,
)


def _tiny_spec(**kw):
    base = dict(
        name="tiny",
        system="adfll",
        task_set="paper8",
        n_tasks=2,
        n_patients=8,
        dqn=TINY_DQN,
        sys=TINY_SYS,
        eval_patients=2,
        eval_episodes=2,
    )
    base.update(kw)
    return ScenarioSpec(**base)


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------
def test_counters_accumulate_per_label_set():
    reg = MetricsRegistry()
    reg.count("comm.bytes", 10, plane="erb")
    reg.count("comm.bytes", 5, plane="erb")
    reg.count("comm.bytes", 7, plane="weights")
    assert reg.counter_value("comm.bytes", plane="erb") == 15
    assert reg.counter_value("comm.bytes", plane="weights") == 7
    assert reg.counters_by_label("comm.bytes", "plane") == {
        "erb": 15,
        "weights": 7,
    }


def test_gauges_overwrite_and_histograms_aggregate():
    reg = MetricsRegistry()
    reg.gauge("queue.depth", 3)
    reg.gauge("queue.depth", 9)
    assert reg.gauge_value("queue.depth") == 9
    for v in (0.5, 1.5, 200.0):
        reg.observe("round.duration", v)
    h = reg.histogram("round.duration")
    assert h["count"] == 3
    assert h["sum"] == pytest.approx(202.0)
    assert sum(h["buckets"].values()) == 3


def test_label_cardinality_is_bounded_not_fatal():
    reg = MetricsRegistry(max_series=4)
    for i in range(20):
        reg.count("requests", 1, user=f"u{i}")
    # per-metric admission: at most max_series live series, the rest
    # counted as dropped — never an exception on the hot path
    assert reg.n_series == 4
    assert reg.n_dropped_series == 16
    assert reg.counter_value("requests", user="u0") == 1
    assert reg.counter_value("requests", user="u19") == 0


def test_null_bundle_is_inert():
    assert NULL.enabled is False
    NULL.count("x", 1)
    NULL.observe("y", 2.0)
    NULL.span("s", "track", 0.0, 1.0)
    NULL.instant("i", "track", 0.0)
    assert len(NULL.tracer) == 0
    assert NULL.summary()["n_events"] == 0
    assert list(NULL.registry.rows()) == []


def test_tracer_event_cap_drops_and_counts():
    tel = Telemetry(enabled=True, max_events=8)
    for i in range(20):
        tel.instant("tick", "t", float(i))
    assert len(tel.tracer) == 8
    assert tel.tracer.n_dropped == 12


# ---------------------------------------------------------------------------
# observe-only contract
# ---------------------------------------------------------------------------
def _fingerprint(report):
    s = dict(report.summary())
    s.pop("extra", None)
    curve = [
        (p.t, p.mean_err, tuple(sorted(p.per_agent.items())))
        for p in report.eval_curve
    ]
    hist = [
        (r.agent_id, r.task, r.start, r.end, r.n_incoming, r.loss)
        for r in report.history
    ]
    return json.dumps(s, sort_keys=True, default=str), curve, hist


def test_disabled_telemetry_is_bit_identical():
    base = run(_tiny_spec())
    tel = Telemetry(enabled=False)
    off = run(_tiny_spec(), telemetry=tel)
    assert _fingerprint(base) == _fingerprint(off)
    # the fleet's collect and flush spans ran on the disabled bundle and
    # recorded nothing
    assert len(tel.tracer) == 0 and list(tel.registry.rows()) == []
    with tel.wall_span("fleet.flush", "fleet", jobs=1) as attrs:
        assert attrs is None
    assert len(tel.tracer) == 0


def test_enabled_telemetry_is_observe_only_and_captures_spans(tmp_path):
    base = run(_tiny_spec())
    tel = Telemetry(enabled=True)
    traced = run(_tiny_spec(), telemetry=tel)
    assert _fingerprint(base) == _fingerprint(traced)
    names = {e["name"] for e in tel.tracer.events}
    assert "round" in names
    assert traced.extra["telemetry"]["n_events"] == len(tel.tracer)
    # registry carries the same byte totals the report already reports
    erb = tel.registry.counter_value("comm.bytes", plane="erb")
    assert erb == traced.summary()["bytes_by_plane"].get("erb", 0)


def test_wall_span_records_attributes_set_in_its_body():
    tel = Telemetry(enabled=True)
    with tel.wall_span("fleet.collect", "fleet", agents=2) as attrs:
        attrs["h2d_bytes"] = 1024
    (span,) = tel.tracer.spans("fleet.collect")
    assert span["clock"] == "wall" and span["track"] == "fleet"
    assert span["args"] == {"agents": 2, "h2d_bytes": 1024}
    assert 0.0 <= span["t0"] <= span["t1"]


def test_wall_span_is_an_annotation_in_the_profiler_trace(tmp_path):
    """A recorded profiler trace holds each wall span as a host event of
    the same name, on the trace's own clock: durations and the distance
    between two spans' starts agree with the tracer's within 1 ms."""
    import time

    import jax
    from jax.profiler import ProfileData

    tel = Telemetry(enabled=True)
    jax.profiler.start_trace(str(tmp_path))
    with tel.wall_span("probe.a", "fleet"):
        time.sleep(0.02)
    time.sleep(0.01)
    with tel.wall_span("probe.b", "fleet"):
        time.sleep(0.03)
    jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    host = {
        ev.name: (ev.start_ns * 1e-9, ev.duration_ns * 1e-9)
        for plane in ProfileData.from_file(str(path)).planes
        for line in plane.lines
        for ev in line.events
        if ev.name in ("probe.a", "probe.b")
    }
    a, b = tel.tracer.spans("probe.a")[0], tel.tracer.spans("probe.b")[0]
    assert set(host) == {"probe.a", "probe.b"}
    assert host["probe.a"][1] == pytest.approx(a["t1"] - a["t0"], abs=1e-3)
    assert host["probe.b"][1] == pytest.approx(b["t1"] - b["t0"], abs=1e-3)
    assert host["probe.b"][0] - host["probe.a"][0] == pytest.approx(
        b["t0"] - a["t0"], abs=1e-3
    )


# ---------------------------------------------------------------------------
# export round-trips
# ---------------------------------------------------------------------------
def _sample_bundle():
    tel = Telemetry(enabled=True)
    tel.span("round", "agent0", 0.0, 1.5, task="t1", round_idx=0)
    tel.span("round", "agent1", 0.5, 2.0, task="t2", round_idx=0)
    tel.span("fleet.flush", "fleet", 0.01, 0.02, clock="wall", jobs=2)
    tel.instant("hub_sync", "scheduler", 1.0)
    tel.count("comm.bytes", 1234, plane="erb")
    tel.observe("round.duration", 1.5)
    return tel


@pytest.mark.parametrize("suffix", [".json", ".jsonl"])
def test_trace_roundtrip(tmp_path, suffix):
    tel = _sample_bundle()
    path = tmp_path / f"trace{suffix}"
    write_trace(tel, path)
    doc = load_trace(path)
    spans = [e for e in doc["events"] if e["kind"] == "span"]
    instants = [e for e in doc["events"] if e["kind"] == "instant"]
    assert sorted(e["name"] for e in spans) == ["fleet.flush", "round", "round"]
    assert [e["name"] for e in instants] == ["hub_sync"]
    tracks = {e["track"] for e in doc["events"]}
    assert tracks == {"agent0", "agent1", "fleet", "scheduler"}
    counters = [m for m in doc["metrics"] if m["kind"] == "counter"]
    assert any(
        m["name"] == "comm.bytes" and m["value"] == 1234 for m in counters
    )


def test_perfetto_document_shape():
    doc = to_perfetto(_sample_bundle())
    events = doc["traceEvents"]
    # one metadata pair (process_name, thread_name) per track + the data
    assert {e["ph"] for e in events} <= {"X", "i", "M", "C"}
    complete = [e for e in events if e["ph"] == "X"]
    assert all(e["dur"] >= 0 and e["ts"] >= 0 for e in complete)
    # sim and wall clocks land in different synthetic processes
    pids = {e["pid"] for e in complete}
    assert len(pids) == 2


def test_sim_and_wall_spans_do_not_share_a_track():
    doc = to_perfetto(_sample_bundle())
    by_key = {}
    for e in doc["traceEvents"]:
        if e["ph"] == "X":
            by_key.setdefault((e["pid"], e["tid"]), set()).add(e["name"])
    for names in by_key.values():
        assert not ({"round", "fleet.flush"} <= names)


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------
def test_cli_summarize_export_diff(tmp_path, capsys):
    a = tmp_path / "a.json"
    b = tmp_path / "b.jsonl"
    write_trace(_sample_bundle(), a)
    assert tel_main(["summarize", str(a)]) == 0
    out = capsys.readouterr().out
    assert "round" in out and "comm.bytes" in out
    assert tel_main(["export", str(a), str(b)]) == 0
    assert b.exists()
    assert len(load_trace(b)["events"]) == len(load_trace(a)["events"])
    assert tel_main(["diff", str(a), str(b)]) == 0
    assert tel_main(["summarize", str(tmp_path / "missing.json")]) == 2
