"""The FLOP and byte counters against hand counts, and their source: the
configuration's own reference."""

import json

import pytest

from harness import flops, loading
from harness.loading import BENCH

DATA = BENCH / "tests" / "data"


def cfg(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def test_wide_macs_per_sample_by_hand():
    # 45 -> 23 -> 12 -> 6 -> 3 per side; out voxels * 27 * c_in * c_out
    conv = 23**3 * 27 * 1 * 32 + 12**3 * 27 * 32 * 32 + 6**3 * 27 * 32 * 64 + 3**3 * 27 * 64 * 64
    fc = (3**3 * 64 + 16) * 512 + 512 * 256 + 256 * 128 + 128 * 6 + 3 * 16
    assert flops.macs_per_sample(cfg("dqn45_wide")) == conv + fc == 74_275_536


def test_base_macs_per_sample_by_hand():
    # the program's DQNConfig widths at 45^3
    base = {
        "reference": "dqn", "box_size": [45, 45, 45], "conv_features": [8, 16, 32], "hidden": [128, 64], "n_actions": 6,
    }
    conv = 23**3 * 27 * 1 * 8 + 12**3 * 27 * 8 * 16 + 6**3 * 27 * 16 * 32
    fc = (6**3 * 32 + 16) * 128 + 128 * 64 + 64 * 6 + 3 * 16
    assert flops.macs_per_sample(base) == conv + fc == 12_481_432
    assert flops.row_floats(base) == 182_259  # the row is the crop's, not the network's


def test_train_flops():
    c = cfg("dqn45_wide")
    # forward + backward (3 forwards) + target forward, 2 FLOPs per MAC
    assert flops.train_flops_per_agent_step(c) == 2 * 4 * 48 * flops.macs_per_sample(c)
    assert flops.train_flops_per_agent_step(c) == 28_521_805_824


def test_pool_row_bytes_at_45():
    c = cfg("dqn45_wide")
    assert flops.row_floats(c) == 182_259
    assert flops.pool_row_bytes(c) == 729_088  # 182,272 lanes of f32
    assert flops.gather_bytes(c, 10) == 2 * 10 * 729_088


def test_tiny_counts():
    c = json.loads((DATA / "configs" / "tiny.json").read_text())
    # 5 -> 3 -> 2 per side; dense (2^3 * 8 + 16) -> 16 -> 6
    assert flops.macs_per_sample(c) == 3**3 * 27 * 4 + 2**3 * 27 * 4 * 8 + 80 * 16 + 16 * 6 + 48 == 11_252
    assert flops.train_flops_per_agent_step(c) == 720_128
    assert flops.row_floats(c) == 2 * 125 + 9 == 259
    assert flops.pool_row_bytes(c) == 1_536


REFERENCE = '''
def macs_per_sample(cfg):
    return 1000 * cfg["scale"]


def row_floats(cfg):
    return 300
'''


def test_counts_are_the_references_own(tmp_path, monkeypatch):
    """A configuration whose reference counts otherwise gets that
    reference's counts, by the name the configuration gives."""
    (tmp_path / "other.py").write_text(REFERENCE)
    monkeypatch.setattr(loading, "REFERENCES", tmp_path)
    c = dict(cfg("dqn45_wide"), reference="other", scale=3)
    assert flops.macs_per_sample(c) == 3000
    assert flops.train_flops_per_agent_step(c) == 2 * 3000 * 48 * 4
    assert flops.row_floats(c) == 300
    assert flops.pool_row_bytes(c) == 384 * 4
    assert flops.gather_bytes(c, 2) == 4 * 384 * 4


def test_unknown_reference_is_an_error(tmp_path, monkeypatch):
    monkeypatch.setattr(loading, "REFERENCES", tmp_path)
    with pytest.raises(FileNotFoundError):
        flops.macs_per_sample(dict(cfg("dqn45_wide"), reference="absent"))
