"""The driver takes the pool's row width from the program, as the
federation's engine computes it, and the observation's shape and the
row's layout from the configuration's reference; set-up stops where the
two disagree, and builds every replay pool a job of the run's seed
builds."""

import types

import numpy as np
import pytest

from harness.loading import BENCH, Catalog
from harness.program import import_path

from drivers import fed  # noqa: E402

DATA = BENCH / "tests" / "data"
SEED = 2**31 + 3


@pytest.fixture(scope="module")
def cat():
    return Catalog(DATA, DATA / "BENCHMARK.json")


def test_setup_builds_the_width_the_engine_computes(cat):
    import_path()
    from repro.core.erb import flat_width
    from repro.kernels.replay_gather.ops import pool_width
    from repro.rl.fleet import FleetEngine

    session = fed.Session(cat.config("tiny_deep"), cat.traffic("fed_small"), SEED, cat.reference("dqn"))
    try:
        assert session.width == FleetEngine(session.dqn)._feat == pool_width(flat_width((6, 6, 6)))
    finally:
        session.free()


def test_setup_warms_every_pool_the_jobs_build(cat):
    """The twin that set-up runs records every pool a real job of the run's
    seed builds: each job of ``warm_jobs`` run in full at the cell's own
    network and sizes builds no buffer-size sequence set-up missed."""
    cfg, mix = cat.config("tiny"), cat.traffic("fed_small")
    session = fed.Session(cfg, mix, SEED, cat.reference("dqn"))
    try:
        for j in range(mix["warm_jobs"]):
            real = fed.run_recorded(session._system(fed.job_seed(SEED, j), telemetry=False), cfg["rounds"])
            assert fed.pool_sequences(real) <= session.pool_sequences, j
    finally:
        session.free()


def stub(ref, obs=None, drop=()):
    """A reference of another observation shape, or whose row leaves out
    the fields ``drop``, for a program that keeps the tiny cell's."""
    def cfg_of(cfg):
        return cfg if obs is None else dict(cfg, box_size=list(obs))

    def row_fields(cfg):
        return [f for f in ref.row_fields(cfg_of(cfg)) if f[0] not in drop]

    return types.SimpleNamespace(
        obs_shape=lambda cfg: ref.obs_shape(cfg_of(cfg)),
        row_fields=row_fields,
        row_floats=lambda cfg: sum(w for _, w in row_fields(cfg)),
    )


@pytest.mark.parametrize(
    "wrong, message",
    [
        # a 6^3 crop: the reference's row is wider than the program's
        ({"obs": (6, 6, 6)}, r"'tiny': the program's pool row is 384 floats wide, its reference's row of 441 "),
        # each crop held once: the reference's row is narrower
        ({"drop": ("next_obs",)}, r"'tiny': the program's pool row is 384 floats wide, its reference's row of 134 "),
        # as many floats in another shape: the row agrees, the network does not
        ({"obs": (25, 5, 1)}, r"'tiny': the program's collection step takes no observation of its reference's "
                              r"obs_shape \(25, 5, 1\)"),
    ],
)
def test_setup_stops_where_reference_and_program_disagree(cat, wrong, message):
    """Set-up stops, before any window, with a ``ValueError`` naming the
    configuration and both sides, where the reference's observation or
    row is not the program's."""
    import_path()
    ref = stub(cat.reference("dqn"), **wrong)
    with pytest.raises(ValueError, match=message):
        fed.Session(cat.config("tiny"), cat.traffic("fed_small"), SEED, ref)


@pytest.mark.parametrize("order", [1, -1])
def test_flat_rows_follow_the_reference_fields(cat, order):
    """``flat_rows`` lays a buffer's rows out field by field in the order
    ``row_fields`` gives, then zero lanes."""
    import_path()
    from repro.core.erb import erb_add, erb_init
    from repro.rl.synth import paper_eight_tasks

    cfg, ref = cat.config("tiny_deep"), cat.reference("dqn")
    obs = ref.obs_shape(cfg)
    erb = erb_init(4, obs, task=next(iter(paper_eight_tasks())))
    rng = np.random.default_rng(0)
    erb_add(erb, {k: rng.integers(0, 6, v[:3].shape).astype(v.dtype) for k, v in erb.data.items()})
    fields = ref.row_fields(cfg)[::order]
    rows = fed.flat_rows(erb, fields, 512)
    o = 0
    for key, width in fields:
        np.testing.assert_array_equal(rows[:, o : o + width], erb.data[key][:3].reshape(3, -1))
        o += width
    assert o == ref.row_floats(cfg) and not rows[:, o:].any()


def test_flat_rows_refuse_a_field_of_another_width(cat):
    import_path()
    from repro.core.erb import erb_add, erb_init
    from repro.rl.synth import paper_eight_tasks

    cfg, ref = cat.config("tiny"), cat.reference("dqn")
    erb = erb_init(4, (6, 6, 6), task=next(iter(paper_eight_tasks())))
    erb_add(erb, {k: v[:2] for k, v in erb.data.items()})
    with pytest.raises(ValueError, match="'obs' is 216 floats wide, the row lays out 125"):
        fed.flat_rows(erb, ref.row_fields(cfg), 512)
