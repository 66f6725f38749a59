"""Find a cell's parts by name: configuration, traffic mix, driver,
reference, limits and per-layer metric readers.

Everything that belongs to one configuration, one mix or one metric sits
in a file of its own under the benchmark's directory, so a later change
adds a cell or a metric by adding files:

* ``configs/<config>.json``  — sizes, source, ``reduced``, ``assumed``;
  ``"reference"`` names the plain reference in ``references/``. The
  ``REQUIRED_KEYS`` of ``harness/program.py`` sit at the top level; an
  ``"agent"`` block gives the network's other ``DQNConfig`` fields, and a
  key there that the program lacks stops set-up.
* ``references/<reference>.py`` — the plain reference of one network,
  importing nothing of the program. It defines ``MODES`` (its precision
  modes, the default and reference first), ``init_params(seed, cfg)``,
  ``train(cfg, params, pool, idx, count0, mode)`` (one agent's steps over
  flat pool rows: losses, the first gradient's per-leaf norms, each
  leaf's change norm after each step), ``qvals(cfg, params, obs, loc,
  mode)``, ``obs_shape(cfg)`` (the shape of one observation as the
  network reads it: the driver lowers the collection step and fills its
  warm buffers at this shape), ``row_fields(cfg)`` (the replay row's
  fields and their flat widths, in the order the pool lays them out: the
  driver reads the pool's rows back by it), ``macs_per_sample(cfg)`` and
  ``row_floats(cfg)`` (the counts ``harness/flops.py`` turns into FLOPs
  and pool bytes). Set-up stops where the program's pool row is not
  ``row_floats`` lane-padded, or its collection step takes no
  ``obs_shape`` observation. A new architecture is a new configuration, a
  new reference and a new limits file.
* ``traffic/<mix>.json``     — the mix's parameters; ``"driver"`` names
  the general generator in ``drivers/`` that reads them.
* ``limits/<cell>.json``     — the limit of each number compared for
  ``correct``, with the readings it was set from.
* ``metrics/<metric>.py``    — one reader, ``read(run) -> float | None``.
"""

from __future__ import annotations

import importlib.util
import json
from functools import lru_cache
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
CHECKOUT = BENCH.parent
REFERENCES = BENCH / "references"


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    if not path.is_file():
        raise FileNotFoundError(f"no such file: {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@lru_cache(maxsize=None)
def _load_reference(path: Path):
    return load_module(path, f"bench_ref_{path.stem}")


def reference(name: str):
    """The plain reference ``references/<name>.py``, loaded once."""
    return _load_reference(REFERENCES / f"{name}.py")


class Catalog:
    """The benchmark's files under ``root`` (the benchmark directory by
    default; tests point it at a directory of small cells)."""

    def __init__(self, root: Path = BENCH, benchmark: Path | None = None):
        self.root = Path(root)
        self.benchmark = load_json(benchmark or CHECKOUT / "BENCHMARK.json")

    def workload(self, name: str) -> dict:
        for w in self.benchmark["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload named {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        return load_json(self.root / "configs" / f"{name}.json")

    def traffic(self, name: str) -> dict:
        return load_json(self.root / "traffic" / f"{name}.json")

    def limits(self, cell: str) -> dict:
        return load_json(self.root / "limits" / f"{cell}.json")["limits"]

    def driver(self, name: str):
        return load_module(BENCH / "drivers" / f"{name}.py", f"bench_driver_{name}")

    def reference(self, name: str):
        return reference(name)

    def end_to_end(self, cell: str) -> list[dict]:
        return [
            m
            for m in self.benchmark["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]
        ]

    def per_layer(self, cell: str) -> list[dict]:
        e2e = {m["name"] for m in self.end_to_end(cell)}
        return [
            m
            for m in self.benchmark["per_layer"]
            if (cell in m["workloads"] if "workloads" in m else m["moves"] in e2e)
        ]

    def reader(self, metric: str):
        return load_module(BENCH / "metrics" / f"{metric}.py", f"bench_metric_{metric}")
