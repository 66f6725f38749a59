"""The sweep subsystem: deterministic grid expansion (within and across
processes), the JSONL report store and resume semantics, the scipy-free
stats against precomputed references, significance-aware aggregation,
report diffing, and both CLIs' unknown-name handling."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from repro.core.experiment import EvalPoint, Report
from repro.experiments import runner
from repro.experiments.suggest import close_matches, unknown_name_message
from repro.sweeps import (
    ReportStore,
    SweepSpec,
    SweepVariant,
    apply_overrides,
    compare,
    forgetting_of,
    get_sweep,
    list_sweeps,
    mean_ci,
    paired_permutation_test,
    paired_ttest,
    run_sweep,
    spec_hash,
    summarize,
    t_crit,
)
from repro.sweeps.__main__ import main as sweeps_cli_main
from repro.sweeps.executor import failed_cells
from repro.sweeps.registry import _REGISTRY
from repro.sweeps.store import STATUS_BUDGET, STATUS_ERROR, STATUS_OK

# ---------------------------------------------------------------------------
# grid expansion determinism
# ---------------------------------------------------------------------------
def test_expansion_is_deterministic_and_fast_variant_is_distinct():
    sw = get_sweep("ci_smoke")
    g1, g2 = sw.expand(fast=True), sw.expand(fast=True)
    assert [c.key for c in g1] == [c.key for c in g2]
    # variants outer, seeds inner
    assert [(c.label, c.seed) for c in g1] == [
        (v.label, s) for v in sw.variants for s in sw.seeds
    ]
    # the fast grid must never collide with the full grid in the store
    full = {c.key for c in sw.expand(fast=False)}
    assert full.isdisjoint({c.key for c in g1})
    # every cell spec carries its own seed (spec and sys in lockstep)
    for c in g1:
        assert c.spec.seed == c.seed and c.spec.sys.seed == c.seed


def test_expansion_keys_are_stable_across_processes():
    """The store key must not depend on PYTHONHASHSEED or process state —
    resuming an interrupted sweep from another process hinges on it."""
    sw = get_sweep("ci_smoke")
    here = [c.key for c in sw.expand(fast=True)]
    code = (
        "from repro.sweeps import get_sweep;"
        "print('\\n'.join(c.key for c in get_sweep('ci_smoke').expand(fast=True)))"
    )
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        env=dict(os.environ, PYTHONPATH="src", PYTHONHASHSEED="271828"),
        check=True,
    )
    assert out.stdout.split() == here


def test_apply_overrides_nested_and_unknown_paths():
    sw = get_sweep("ci_smoke")
    cell = sw.expand()[0]
    assert cell.spec.sys.rounds == 2  # the smoke override applied
    assert cell.spec.n_tasks == 2
    base = cell.spec
    over = apply_overrides(base, (("dqn.batch_size", 4), ("n_patients", 8)))
    assert over.dqn.batch_size == 4 and over.n_patients == 8
    with pytest.raises(ValueError, match="no field"):
        apply_overrides(base, (("sys.bogus_field", 1),))
    with pytest.raises(ValueError, match="no field"):
        apply_overrides(base, (("bogus", 1),))


def test_sweep_spec_validation():
    v = SweepVariant("a", "paper_fig2")
    with pytest.raises(ValueError, match="duplicate"):
        SweepSpec(name="x", variants=(v, SweepVariant("a", "baseline_partial")))
    with pytest.raises(ValueError, match="seed"):
        SweepSpec(name="x", variants=(v,), seeds=())
    with pytest.raises(ValueError, match="baseline"):
        SweepSpec(name="x", variants=(v,), baseline="nope")
    with pytest.raises(ValueError, match="no variants"):
        SweepSpec(name="x")


def test_builtin_sweeps_cover_the_paper_claims():
    names = {s.name for s in list_sweeps()}
    assert {"paper_table1_sweep", "paper_table2_hub_failure", "ci_smoke"} <= names
    t1 = get_sweep("paper_table1_sweep")
    assert len(t1.seeds) >= 5 and t1.baseline == "adfll"
    assert {v.scenario for v in t1.variants} == {
        "paper_fig2",
        "baseline_all_knowing",
        "baseline_partial",
        "baseline_sequential",
    }
    t2 = get_sweep("paper_table2_hub_failure")
    assert {v.scenario for v in t2.variants} >= {
        "paper_table2_hub_failure",
        "paper_table2_hybrid_failover",
    }
    assert get_sweep("ci_smoke").cell_budget_s is not None


# ---------------------------------------------------------------------------
# stats: precomputed references + edge cases
# ---------------------------------------------------------------------------
A5 = [7.2, 8.1, 6.9, 7.8, 7.4]
B5 = [15.3, 14.8, 16.1, 15.0, 14.6]


def test_paired_ttest_matches_reference():
    t, p = paired_ttest(A5, B5)
    assert t == pytest.approx(-17.373964922078468, abs=1e-12)
    assert p == pytest.approx(6.442051303582614e-05, rel=1e-9)
    # symmetry
    t2, p2 = paired_ttest(B5, A5)
    assert t2 == pytest.approx(-t) and p2 == pytest.approx(p)


def test_permutation_test_exact_small_sample():
    # n=5: all 32 sign patterns enumerated; every |mean| <= the observed
    # one except none -> only the two all-same patterns reach it: 2/32
    assert paired_permutation_test(A5, B5) == pytest.approx(0.0625)
    assert paired_permutation_test(B5, A5) == pytest.approx(0.0625)


def test_permutation_test_monte_carlo_branch_is_seeded():
    rng = np.random.default_rng(0)
    a = rng.normal(0.0, 1.0, 20)
    b = a + rng.normal(1.0, 0.3, 20)  # strong paired shift
    p1 = paired_permutation_test(a, b, n_resamples=2000, seed=7)
    p2 = paired_permutation_test(a, b, n_resamples=2000, seed=7)
    assert p1 == p2  # seeded Monte Carlo
    assert 0.0 < p1 < 0.01  # add-one estimator keeps p > 0


def test_stats_edge_cases_n_lt_2_and_zero_variance():
    t, p = paired_ttest([1.0], [2.0])
    assert np.isnan(t) and np.isnan(p)
    assert paired_ttest([1, 2, 3], [1, 2, 3]) == (0.0, 1.0)
    assert paired_permutation_test([1.0], [2.0]) == 1.0
    assert paired_permutation_test([1, 2, 3], [1, 2, 3]) == 1.0
    m, hw = mean_ci([])
    assert np.isnan(m) and np.isnan(hw)
    m, hw = mean_ci([5.0])
    assert m == 5.0 and np.isnan(hw)
    assert mean_ci([2.0, 2.0, 2.0]) == (2.0, 0.0)


def test_mean_ci_matches_reference():
    m, hw = mean_ci(A5)
    assert m == pytest.approx(7.48)
    assert m - hw == pytest.approx(6.888415185314209, abs=1e-9)
    assert t_crit(0.05, 4) == pytest.approx(2.7764451051977863, abs=1e-9)


# ---------------------------------------------------------------------------
# report store
# ---------------------------------------------------------------------------
def _row(key, status=STATUS_OK, err=7.0, seed=0, label="v"):
    return {
        "key": key,
        "label": label,
        "scenario": "s",
        "seed": seed,
        "status": status,
        "elapsed_s": 0.1,
        "summary": {"mean_dist_err": err},
    }


def test_store_roundtrip_last_row_wins_and_torn_tail(tmp_path):
    store = ReportStore(tmp_path / "s.jsonl")
    assert store.load() == {}
    store.append(_row("k1", status=STATUS_ERROR))
    store.append(_row("k2"))
    store.append(_row("k1"))  # retry superseded the failure
    with open(store.path, "a") as f:
        f.write('{"key": "k3", "status"')  # crash mid-append
    rows = store.load()
    assert set(rows) == {"k1", "k2"}
    assert rows["k1"]["status"] == STATUS_OK
    assert set(store.completed()) == {"k1", "k2"}
    with pytest.raises(ValueError):
        store.append({"status": "ok"})
    assert store.prune(["k2"]) == 1
    assert set(store.load()) == {"k2"}


# ---------------------------------------------------------------------------
# executor: resume, budgets, failures (runner stubbed; workers=1 inline)
# ---------------------------------------------------------------------------
def _tiny_sweep(**kw):
    base = dict(
        name="t",
        variants=(
            SweepVariant("a", "plane_erb_only"),
            SweepVariant("b", "topo_gossip"),
        ),
        seeds=(0, 1),
        baseline="a",
    )
    base.update(kw)
    return SweepSpec(**base)


def _fake_report(spec):
    rep = Report(scenario=spec.name, system=spec.system, seed=spec.seed)
    rep.mean_dist_err = 5.0 + spec.seed + (0.5 if "gossip" in spec.name else 0.0)
    rep.best_agent_err = rep.mean_dist_err
    rep.makespan = 2.0
    rep.eval_curve = [EvalPoint(t=2.0, n_agents=1, mean_err=rep.mean_dist_err)]
    return rep


def test_run_sweep_executes_resumes_and_aggregates(tmp_path, monkeypatch):
    calls = []

    def fake_run(spec, **kw):
        calls.append(spec.name)
        return _fake_report(spec)

    monkeypatch.setattr(runner, "run", fake_run)
    sw = _tiny_sweep()
    store = ReportStore(tmp_path / "t.jsonl")
    summary = run_sweep(sw, workers=1, store=store)
    assert len(calls) == 4 and not failed_cells(summary)
    assert summary["variants"]["a"]["n_ok"] == 2
    st = summary["variants"]["a"]["metrics"]["mean_dist_err"]
    assert st["mean"] == pytest.approx(5.5) and st["n"] == 2
    assert st["values"] == {"0": 5.0, "1": 6.0}
    # paired comparison exists against the baseline
    comps = {
        (c["variant"], c["metric"]): c for c in summary["comparisons"]
    }
    assert comps[("b", "mean_dist_err")]["delta"] == pytest.approx(0.5)

    # resume: all four cells cached, nothing re-executed
    calls.clear()
    summary2 = run_sweep(sw, workers=1, store=store)
    assert calls == []
    assert all(c["cached"] for c in summary2["cells"])
    assert summary2["variants"] == summary["variants"]

    # partial resume: drop one cell from the store -> exactly one re-runs
    keys = [c.key for c in sw.expand()]
    store.prune(keys[1:])
    summary3 = run_sweep(sw, workers=1, store=store)
    assert calls == ["plane_erb_only"]
    assert sum(not c["cached"] for c in summary3["cells"]) == 1


def test_budget_exceeded_marks_the_cell_failed(tmp_path, monkeypatch):
    def slow_run(spec, **kw):
        import time

        time.sleep(5.0)  # far past the budget: the alarm must interrupt
        return _fake_report(spec)

    monkeypatch.setattr(runner, "run", slow_run)
    sw = _tiny_sweep(seeds=(0,), cell_budget_s=0.05)
    t0 = time.monotonic()
    summary = run_sweep(sw, workers=1)
    bad = failed_cells(summary)
    assert len(bad) == 2
    assert all(c["status"] == STATUS_BUDGET for c in bad)
    # enforcement is real: the cells were interrupted, not slept to completion
    assert time.monotonic() - t0 < 4.0
    # over-budget cells contribute no metrics
    assert summary["variants"]["a"]["n_ok"] == 0
    assert summary["variants"]["a"]["metrics"]["mean_dist_err"]["mean"] is None


def test_worker_exception_records_an_error_cell(tmp_path, monkeypatch):
    def boom(spec, **kw):
        raise RuntimeError("kaboom")

    monkeypatch.setattr(runner, "run", boom)
    sw = _tiny_sweep(seeds=(0,))
    store = ReportStore(tmp_path / "t.jsonl")
    summary = run_sweep(sw, workers=1, store=store)
    bad = failed_cells(summary)
    assert {c["status"] for c in bad} == {STATUS_ERROR}
    # failed rows persist but do not count as completed -> retried next run
    assert store.completed() == {}

    monkeypatch.setattr(runner, "run", lambda spec, **kw: _fake_report(spec))
    summary2 = run_sweep(sw, workers=1, store=store)
    assert not failed_cells(summary2)


def test_accelerator_host_runs_cells_inline(monkeypatch):
    # off the CPU a spawned worker would need the chip the parent holds:
    # the sweep runs inline (the stubbed runner is only visible here)
    calls = []

    def fake_run(spec, **kw):
        calls.append(spec.name)
        return _fake_report(spec)

    monkeypatch.setattr(runner, "run", fake_run)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    said = []
    summary = run_sweep(_tiny_sweep(seeds=(0,)), workers=4, echo=said.append)
    assert len(calls) == 2 and not failed_cells(summary)
    assert any("inline" in line for line in said)


# ---------------------------------------------------------------------------
# aggregation + compare
# ---------------------------------------------------------------------------
def test_forgetting_of_curve_shapes():
    def s(errs):
        return {"eval_curve": [{"mean_err": e} for e in errs]}

    assert forgetting_of(s([8.0, 5.0, 7.0])) == pytest.approx(2.0)
    assert forgetting_of(s([8.0, 5.0])) == 0.0  # final is the best
    assert forgetting_of(s([6.0])) == 0.0
    assert forgetting_of({"eval_curve": []}) is None


def _summary_with(err_by_label_seed, sweep=None):
    sw = sweep or _tiny_sweep()
    rows = []
    for (label, seed), err in err_by_label_seed.items():
        cell = next(c for c in sw.expand() if c.label == label and c.seed == seed)
        rows.append(_row(cell.key, err=err, seed=seed, label=label))
    return summarize(sw, rows)


def test_compare_flags_significant_regressions(tmp_path):
    sw = _tiny_sweep(seeds=(0, 1, 2, 3, 4), baseline=None)
    a = _summary_with(
        {("a", s): 7.0 + 0.1 * s for s in range(5)}
        | {("b", s): 7.0 + 0.1 * s for s in range(5)},
        sweep=sw,
    )
    b = _summary_with(
        {("a", s): 7.0 + 0.1 * s for s in range(5)}  # unchanged
        | {("b", s): 12.0 + 0.3 * s for s in range(5)},  # much worse
        sweep=sw,
    )
    rows, regs = compare(a, b)
    assert len(regs) == 1
    assert regs[0]["variant"] == "b" and regs[0]["metric"] == "mean_dist_err"
    assert regs[0]["p_ttest"] < 0.05 and regs[0]["delta"] == pytest.approx(5.4)
    # an improvement is significant but NOT a regression
    rows_back, regs_back = compare(b, a)
    assert regs_back == []
    assert any(r["significant"] and not r["regression"] for r in rows_back)

    # the CLI wires this to exit codes
    pa, pb = tmp_path / "a.json", tmp_path / "b.json"
    pa.write_text(json.dumps(a))
    pb.write_text(json.dumps(b))
    assert sweeps_cli_main(["--compare", str(pa), str(pa)]) == 0
    assert sweeps_cli_main(["--compare", str(pa), str(pb)]) == 1
    empty = tmp_path / "empty.json"
    empty.write_text(json.dumps({"variants": {}}))
    assert sweeps_cli_main(["--compare", str(pa), str(empty)]) == 2


def test_single_seed_compare_cannot_reach_significance(tmp_path):
    sw = _tiny_sweep(seeds=(0,), baseline=None)
    a = _summary_with({("a", 0): 7.0, ("b", 0): 7.0}, sweep=sw)
    b = _summary_with({("a", 0): 7.0, ("b", 0): 12.0}, sweep=sw)
    rows, regs = compare(a, b)
    assert regs == []  # n=1: no p-value, never "significant"
    assert all(r["p_ttest"] is None for r in rows)


def test_check_regression_is_ci_aware_for_sweep_summaries(tmp_path):
    from benchmarks.check_regression import compare as gate

    def sweep_doc(mean, ci):
        return {
            "variants": {
                "v": {"metrics": {"mean_dist_err": {"mean": mean, "ci95": ci}}}
            }
        }

    # worse by >20% and >0.75 absolute, but CIs overlap -> pass
    assert gate(sweep_doc(5.0, 0.5), sweep_doc(6.5, 1.5), tol=0.2, abs_floor=0.75) == []
    # same deltas with tight CIs -> fail
    fails = gate(sweep_doc(5.0, 0.1), sweep_doc(6.5, 0.1), tol=0.2, abs_floor=0.75)
    assert len(fails) == 1 and "CIs separated" in fails[0]
    # legacy point-run files keep the original semantics
    legacy_base = {"configs": {"v": {"mean_dist_err": 5.0}}}
    legacy_cur = {"configs": {"v": {"mean_dist_err": 6.5}}}
    assert len(gate(legacy_base, legacy_cur, tol=0.2, abs_floor=0.75)) == 1
    assert gate(legacy_base, legacy_base, tol=0.2, abs_floor=0.75) == []
    # missing config still fails
    assert len(gate(legacy_base, {"configs": {}}, tol=0.2, abs_floor=0.75)) == 1


# ---------------------------------------------------------------------------
# CLIs: suggestions and exit codes
# ---------------------------------------------------------------------------
def test_suggestion_helper():
    assert close_matches("paper_fig3", ["paper_fig2", "topo_hub"]) == ["paper_fig2"]
    msg = unknown_name_message("scenario", "paper_fig3", ["paper_fig2"])
    assert "paper_fig3" in msg and "paper_fig2" in msg
    assert "--list" in unknown_name_message("scenario", "zzz", ["qq"])


def test_sweeps_cli_list_and_unknown_name(capsys):
    assert sweeps_cli_main(["--list"]) == 0
    assert "paper_table1_sweep" in capsys.readouterr().out
    assert sweeps_cli_main(["--sweep", "paper_table1_swep"]) == 2
    assert "did you mean" in capsys.readouterr().err
    assert sweeps_cli_main(["--sweep", "ci_smoke", "--seeds", "0"]) == 2
    assert sweeps_cli_main(["--sweep", "ci_smoke", "--budget", "0"]) == 2


def test_experiments_cli_unknown_scenario_suggests(capsys):
    from repro.experiments.__main__ import main as exp_main

    assert exp_main(["--scenario", "paper_fig3"]) == 2
    err = capsys.readouterr().err
    assert "unknown scenario" in err and "paper_fig2" in err


def test_registry_rejects_duplicate_sweeps():
    sw = next(iter(_REGISTRY.values()))
    from repro.sweeps import register_sweep

    with pytest.raises(ValueError, match="already registered"):
        register_sweep(sw)
