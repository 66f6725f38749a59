"""How a configuration file reaches the program: the required keys and
the ``agent`` block's fields, lists as tuples at every depth; an
``agent`` key the program lacks stops set-up with its name."""

import dataclasses
import importlib.util
import json
import shutil

import jax
import pytest

from harness import program
from harness.loading import BENCH, Catalog
from harness.program import dqn_config, import_path

DATA = BENCH / "tests" / "data"


def cfg(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


@pytest.fixture
def wider(monkeypatch):
    """The program's ``DQNConfig`` with one architecture field more, as a
    program that builds a layer list would have."""
    import_path()
    from repro.configs import adfll_dqn

    @dataclasses.dataclass(frozen=True)
    class Wider(adfll_dqn.DQNConfig):
        layers: tuple = ()

    monkeypatch.setattr(adfll_dqn, "DQNConfig", Wider)
    return Wider


def test_wide_config_is_the_literal():
    import_path()
    from repro.configs.adfll_dqn import DQNConfig

    assert dqn_config(cfg("dqn45_wide")) == DQNConfig(
        volume_shape=(128, 128, 128),
        box_size=(45, 45, 45),
        n_actions=6,
        frame_history=1,
        conv_features=(32, 32, 64, 64),
        hidden=(512, 256, 128),
        gamma=0.9,
        lr=0.001,
        eps_start=1.0,
        eps_end=0.05,
        eps_decay_steps=500,
        target_update=50,
        batch_size=48,
        max_episode_steps=48,
        step_size=1,
    )


def test_extra_field_reaches_the_program_as_tuples(wider):
    layers = [["conv", 5, [1, 1, 1]], ["pool", [2, 2, 2]], ["dense", 512]]
    got = dqn_config(dict(cfg("dqn45_wide"), agent={"layers": layers}))
    assert isinstance(got, wider)
    assert got.layers == (("conv", 5, (1, 1, 1)), ("pool", (2, 2, 2)), ("dense", 512))
    hash(got)  # the fleet's program caches key on the config
    assert got.conv_features == (32, 32, 64, 64)


def test_keys_that_are_no_field_stay_out():
    got = dqn_config(dict(cfg("dqn45_wide"), layers=[1, 2]))
    assert not hasattr(got, "layers") and not hasattr(got, "erb_capacity")


def test_fields_outside_the_agent_block_stay_out(wider):
    """Only the required keys are read at the top level: a field elsewhere
    in the file does not reach the program unasked."""
    assert dqn_config(dict(cfg("dqn45_wide"), layers=[1, 2])).layers == ()


def test_agent_key_the_program_lacks_is_named():
    with pytest.raises(ValueError, match="no field 'layers'"):
        dqn_config(dict(cfg("dqn45_wide"), agent={"layers": [1, 2]}))


def test_required_key_under_agent_is_named():
    with pytest.raises(ValueError, match="'hidden' under agent"):
        dqn_config(dict(cfg("dqn45_wide"), agent={"hidden": [8]}))


def test_required_key_missing_is_named():
    c = cfg("dqn45_wide")
    del c["hidden"]
    with pytest.raises(KeyError, match="hidden"):
        dqn_config(c)


def test_run_stops_at_setup_on_an_agent_key_the_program_lacks(tmp_path):
    """``run.py``'s cell run, on a copy of the test cells whose
    configuration asks for a ``layers`` field: set-up stops before any
    window, with the key named."""
    root = tmp_path / "data"
    shutil.copytree(DATA, root)
    path = root / "configs" / "tiny.json"
    c = json.loads(path.read_text())
    path.write_text(json.dumps(dict(c, agent={"layers": [["conv", 3]]})))
    spec = importlib.util.spec_from_file_location("bench_run", BENCH / "run.py")
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    cat = Catalog(root, root / "BENCHMARK.json")
    with pytest.raises(ValueError, match="no field 'layers'"):
        run.run_cell(cat, "tiny.fed_small", 2**31 + 5, 1.0, False, jax.devices(), log=lambda *_: None)


def test_required_keys_are_program_fields():
    import_path()
    from repro.configs.adfll_dqn import DQNConfig

    assert set(program.REQUIRED_KEYS) <= {f.name for f in dataclasses.fields(DQNConfig)}
