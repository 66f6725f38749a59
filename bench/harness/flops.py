"""Operations and bytes of the agent's programs, from shapes alone.

The counts that depend on the network and the replay row's layout are
the configuration's reference's own (``references/<reference>.py``:
``macs_per_sample(cfg)``, ``row_floats(cfg)``); this module turns them
into a training step's FLOPs and the device pool's bytes. One
multiply-add is two FLOPs.
"""

from __future__ import annotations

from . import loading

LANES = 128  # the device pool's row width is rounded up to whole lanes


def macs_per_sample(cfg: dict) -> int:
    """Multiply-adds of one forward pass over one observation."""
    return loading.reference(cfg["reference"]).macs_per_sample(cfg)


def row_floats(cfg: dict) -> int:
    """Floats of one replay row."""
    return loading.reference(cfg["reference"]).row_floats(cfg)


def train_flops_per_agent_step(cfg: dict) -> int:
    """Model FLOPs of one agent's SGD step: online forward and backward
    (three forward passes' worth) plus the target network's forward, over
    the minibatch."""
    return 2 * macs_per_sample(cfg) * cfg["batch_size"] * 4


def lane_pad(floats: int) -> int:
    """A row of ``floats`` floats as the device pool holds it: rounded up
    to whole lanes."""
    return -(-floats // LANES) * LANES


def pool_row_bytes(cfg: dict) -> int:
    """Bytes of one float32 row of the device pool (lane-padded)."""
    return lane_pad(row_floats(cfg)) * 4


def gather_bytes(cfg: dict, rows: int) -> int:
    """Bytes the replay gather moves for ``rows`` rows: each read from the
    pool and written to the batch once."""
    return 2 * rows * pool_row_bytes(cfg)
