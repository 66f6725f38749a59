"""The driver takes the pool's row width from the program, as the
federation's engine computes it, and set-up builds every replay pool a
job of the run's seed builds."""

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
