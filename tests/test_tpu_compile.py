"""Compile the main path's kernels for a described TPU v5e (no chip needed).

The TPU compiler is installed with jaxlib's TPU support and compiles for
a topology that is described, not attached. These tests catch what the
interpreter cannot: block shapes the chip's compiler refuses, pool-sized
temporaries, kernels inside a program that must be partitioned. Nothing
runs, so they say nothing about results or times.

The topology is described inside a module fixture (never at import) so
that, under several pytest workers, only the worker given this file
loads the TPU library.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.configs.adfll_dqn import DQNConfig
from repro.core.erb import flat_width
from repro.kernels.fused_td.kernel import fused_td
from repro.kernels.replay_gather.ops import TPU_MODE, pool_width, replay_gather
from repro.rl.fleet import FleetSteps

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "collective-permute")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("box", [8, 45], ids=["box8", "box45"])
def test_replay_gather_compiles_without_a_pool_copy(one_chip, box):
    # the paper_fig2 width (8^3 boxes, F=1033) and the paper's 45^3 crops
    # (F=182,259): a 2,048-row pool is 1.5 GB at 45^3
    rows, batch = 2048, 128
    width = pool_width(flat_width((box,) * 3))
    pool = _sds((rows, 1, width), jnp.float32, one_chip)
    idx = _sds((batch,), jnp.int32, one_chip)
    w = _sds((batch,), jnp.float32, one_chip)
    assert TPU_MODE == "compiled"  # the kernel is the TPU choice
    compiled = replay_gather.lower(pool, idx, w, mode=TPU_MODE).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    pool_bytes = rows * width * 4
    # the kernel reads the pool in place: no relayout copy of it
    assert mem.temp_size_in_bytes < pool_bytes // 64, mem
    assert mem.output_size_in_bytes == batch * width * 4


def test_fused_td_compiles(one_chip):
    b, a = 32, 6
    col = _sds((b, 1), jnp.float32, one_chip)
    q_next = _sds((b, a), jnp.float32, one_chip)

    def step(q_sel, q_next, r, d):
        return fused_td(q_sel, q_next, r, d, gamma=0.9, block_b=32, interpret=False)

    compiled = jax.jit(step).lower(col, q_next, col, col).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_chunk_compiles_on_a_2x2_mesh(topo):
    # the fleet chunk on a 4-device mesh is one shard_map program: the
    # Pallas gather sits inside it (Mosaic kernels cannot be partitioned
    # automatically) and the compiler inserts no collective
    cfg = DQNConfig(
        volume_shape=(16, 16, 16),
        box_size=(6, 6, 6),
        conv_features=(4,),
        hidden=(32,),
        batch_size=8,
    )
    mesh = Mesh(np.array(topo.devices), ("fleet",))
    steps = FleetSteps(cfg, False, mesh)
    steps.gather_mode = TPU_MODE  # this process's backend is the CPU
    fs = steps.sharding
    n, k, rows = 16, 4, 256
    state = jax.tree_util.tree_map(
        lambda x: _sds((n, *x.shape[1:]), x.dtype, fs.stacked), steps.init_slot(0)
    )
    width = pool_width(flat_width(cfg.box_size))
    pool = _sds((rows, 1, width), jnp.float32, fs.replicated)
    idx = _sds((k, n, cfg.batch_size), jnp.int32, fs.indices)
    text = steps.train_chunk.lower(state, pool, idx).compile().as_text()
    assert "tpu_custom_call" in text
    found = {c for c in COLLECTIVES if re.search(rf"\b{c}(-start)?\(", text)}
    assert not found, found
