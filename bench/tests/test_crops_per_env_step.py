"""``collect.crops_per_env_step`` on hand-built runs: the window's
``crop_rows`` over its ``env_steps``, and nothing from a program whose
``fleet.collect`` spans carry no ``crop_rows``."""

import importlib.util

import pytest

from harness.loading import BENCH, Catalog
from harness.program import import_path

CELL = "dqn45_wide.fed_paper"
METRIC = "collect.crops_per_env_step"


@pytest.fixture(scope="module")
def run():
    spec = importlib.util.spec_from_file_location("bench_run", BENCH / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def cat():
    return Catalog()


def view(run, cat, spans):
    import_path()
    from repro.telemetry import Telemetry

    tel = Telemetry(enabled=True)
    for name, t0, t1, args in spans:
        tel.span(name, "fleet", t0, t1, clock="wall", **args)
    cfg = cat.config(cat.workload(CELL)["config"])
    result = {"counts": {"window_s": 5.0, "agent_steps": 300, "agent_rounds": 2}}
    return run.RunView(cfg, {}, result, tel, None, None, {"programs_compiled": 0})


FLUSH = {"jobs": 1, "k_steps": 150, "batch": 48, "pool_rows": 256}
# a set-up flush, then two collections: 48 dispatches of 24 rows with the
# start crop, and one whose episodes ended early
COLLECT1 = {"agents": 1, "dispatches": 48, "env_steps": 1152, "crop_rows": 49 * 24}
COLLECT2 = {"agents": 1, "dispatches": 20, "env_steps": 400, "crop_rows": 430}
SPANS = [
    ("fleet.flush", 0.0, 1.0, FLUSH),
    ("fleet.collect", 10.0, 11.0, COLLECT1),
    ("fleet.collect", 11.5, 12.0, COLLECT2),
    ("fleet.flush", 12.0, 13.0, FLUSH),
]


def test_reads_rows_cropped_per_env_step(run, cat):
    got = cat.reader(METRIC).read(view(run, cat, SPANS))
    assert got == pytest.approx((49 * 24 + 430) / (1152 + 400))


def test_twice_per_step_reads_two(run, cat):
    """A collection that crops each step's observation and next
    observation apart reads 2."""
    twice = dict(COLLECT1, crop_rows=2 * 1152)
    spans = [("fleet.collect", 10.0, 11.0, twice)]
    assert cat.reader(METRIC).read(view(run, cat, spans)) == pytest.approx(2.0)


@pytest.mark.parametrize(
    "spans",
    [
        # the parent's collection spans: no crop_rows
        [
            ("fleet.collect", 10.0, 11.0, {k: v for k, v in c.items() if k != "crop_rows"})
            for c in (COLLECT1, COLLECT2)
        ],
        # no collection at all
        [SPANS[0]],
        # a collection that stepped nothing
        [("fleet.collect", 10.0, 11.0, dict(COLLECT1, env_steps=0, crop_rows=0))],
    ],
    ids=["no_attribute", "no_collection", "no_steps"],
)
def test_reads_nothing_without_crop_rows(run, cat, spans):
    assert cat.reader(METRIC).read(view(run, cat, spans)) is None


def test_declared_for_the_cell(cat):
    declared = {m["name"]: m for m in cat.per_layer(CELL)}
    m = declared[METRIC]
    assert (m["layer"], m["unit"], m["better"], m["source"], m["moves"]) == (
        "collection",
        "rows",
        "lower",
        "program_span",
        "train_steps_per_s",
    )
    assert m["workloads"] == [CELL]
    assert (BENCH / "metrics" / f"{METRIC}.py").is_file()
