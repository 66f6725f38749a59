"""The program under test, as the benchmark sees it: its source tree on
the import path and the agent configuration built from a config file."""

from __future__ import annotations

import dataclasses
import sys

from .loading import CHECKOUT

# the keys every configuration file gives; a twin network built for
# set-up's bookkeeping (``drivers/fed.py``) is made from these alone
REQUIRED_KEYS = (
    "volume_shape",
    "box_size",
    "n_actions",
    "frame_history",
    "conv_features",
    "hidden",
    "gamma",
    "lr",
    "eps_start",
    "eps_end",
    "eps_decay_steps",
    "target_update",
    "batch_size",
    "max_episode_steps",
    "step_size",
)


def import_path() -> None:
    src = str(CHECKOUT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import repro.core  # noqa: F401  (the package's core <-> rl import order)


def frozen(value):
    """Lists as tuples at every depth, so the frozen config stays hashable."""
    if isinstance(value, (list, tuple)):
        return tuple(frozen(v) for v in value)
    return value


def dqn_config(cfg: dict):
    """The program's agent configuration from a config file: the
    ``REQUIRED_KEYS`` at its top level, and every key of its optional
    ``"agent"`` block, the network's other fields. Each key of that block
    must be a field of the program's ``DQNConfig`` and none of the
    ``REQUIRED_KEYS``, or this raises a ``ValueError`` naming it (a program
    without that architecture would otherwise train another network under
    the cell's name)."""
    import_path()
    from repro.configs.adfll_dqn import DQNConfig

    name = cfg.get("name", "?")
    agent = cfg.get("agent", {})
    fields = {f.name for f in dataclasses.fields(DQNConfig)}
    lacking = [k for k in agent if k not in fields]
    if lacking:
        raise ValueError(
            f"the program's DQNConfig has no field {', '.join(map(repr, lacking))} "
            f"(under agent of configuration {name!r})"
        )
    twice = [k for k in agent if k in REQUIRED_KEYS]
    if twice:
        raise ValueError(
            f"{', '.join(map(repr, twice))} under agent of configuration {name!r} "
            "belongs at the top level"
        )
    keys = {k: cfg[k] for k in REQUIRED_KEYS} | agent  # a KeyError names a missing one
    return DQNConfig(**{k: frozen(v) for k, v in keys.items()})
