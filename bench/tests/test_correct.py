"""The comparison that decides ``correct``, driven end to end on the CPU
at a size a test run holds: the harness's look for a chip is skipped and
everything else of a run happens. Sound runs come out correct; the
control (the reference computed in float8 in the program's place) and
each fault planted under the timed path come out not correct."""

import importlib.util

import jax
import pytest

from harness.loading import BENCH, Catalog

DATA = BENCH / "tests" / "data"
SEED = 2**31 + 77  # larger than 32 signed bits hold


@pytest.fixture(scope="module")
def run():
    spec = importlib.util.spec_from_file_location("bench_run", BENCH / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def cat():
    return Catalog(DATA, DATA / "BENCHMARK.json")


def one(run, cat, cell, fault=None, trace=False):
    return run.run_cell(cat, cell, SEED, 2.0, trace, jax.devices(), fault=fault, log=lambda *_: None)


# two configurations of one traffic mix through the same driver
CELLS = ["tiny.fed_small", "tiny_deep.fed_small"]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(run, cat, cell):
    out = one(run, cat, cell)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert out["checks"]["rows_unmatched"]["value"] == 0  # rows read back by the reference's row_fields
    assert out["attempted"] > 0 and out["failed"] == 0
    assert "setup_s" in out["metrics"]


@pytest.mark.parametrize(
    "fault, fails",
    [
        ("unchanged", ["dparam_gap"]),  # a step returns its state unchanged
        ("half_batch", ["loss1_gap"]),  # half the batch left out, the mean over the rest
        ("altered", ["collect_q_gap"]),  # a collection answer altered where it is produced
        ("control", ["loss1_gap", "collect_q_gap"]),  # the reference in float8 in the program's place
    ],
)
@pytest.mark.parametrize("cell", CELLS)
def test_planted_fault_is_not_correct(run, cat, cell, fault, fails):
    """Each fault, and the control, is not correct, and fails at least the
    numbers named for it."""
    out = one(run, cat, cell, fault=fault)
    assert not out["correct"], out["checks"]
    for name in fails:
        assert out["checks"][name]["value"] > out["checks"][name]["limit"], (name, out["checks"])


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reports_layers(run, cat, cell):
    out = one(run, cat, cell, trace=True)
    assert out["correct"]
    assert {"fed.host_s_per_round", "flush.ms_per_step", "compiles.train"} <= set(out["metrics"])
    assert out["device"]["window_s"] > 0
