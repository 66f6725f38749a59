"""Sharding policy: logical->mesh rules for params, optimizer state, acts.

2-D param sharding: tensor-parallel over ``model`` + FSDP over ``fsdp_axes``
(default: the ``data`` axis). Any dim that does not divide the assigned
axis size falls back to replication — this keeps small archs (xlstm-125m)
lowering on a 256-chip mesh without bespoke configs.

The fleet engine uses a second, much simpler family defined at the
bottom: a 1-D mesh whose single axis carries the *leading agent axis* of
every stacked pytree (params / target / optimizer / PRNG / counters),
with the replay pool replicated — pure population parallelism, where the
per-slot program is identical on every device and no collective ever
crosses slots (:class:`FleetSharding`, :func:`make_fleet_mesh`).
"""

from __future__ import annotations

import fnmatch
import math
from dataclasses import dataclass

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

FSDP = "__fsdp__"  # placeholder resolved to policy.fsdp_axes
MODEL = "model"
HEADQ = "__headq__"  # model axis iff cfg.n_heads divides it (else replicate)
HEADKV = "__headkv__"  # model axis iff cfg.n_kv_heads divides it
FLEET = "fleet"  # the stacked agent axis of the fleet engine


@dataclass(frozen=True)
class ShardingPolicy:
    batch_axes: tuple[str, ...] = ("data",)
    model_axis: str = "model"
    fsdp_axes: tuple[str, ...] = ("data",)
    seq_shard: bool = True  # sequence-parallel activations at boundaries
    remat: bool = True  # per-layer-group activation checkpointing
    tensor_parallel: bool = True  # False: model axis carries batch (pure DP)
    # perf knobs (hillclimbing)
    q_chunk: int = 512
    kv_chunk: int = 1024
    loss_chunk: int = 2048
    microbatches: int = 1  # gradient accumulation (memory knob)
    # hoist dense-FFN FSDP weight gathers out of the microbatch scan:
    # those weights are kept TP-only-sharded for the whole step, so the
    # ZeRO-3 gather is paid once per step instead of once per microbatch.
    hoist_dense_gathers: bool = False


# (pattern, per-dim template). First match wins. Templates use mesh-axis
# names or FSDP; dims beyond the template are replicated.
_RULES = [
    ("embed/tok", (MODEL, FSDP)),
    ("embed/head/w", (FSDP, MODEL)),
    ("*/wq/w", (FSDP, HEADQ)),
    ("*/wk/w", (FSDP, HEADKV)),
    ("*/wv/w", (FSDP, HEADKV)),
    ("*/wq/b", (HEADQ,)),
    ("*/wk/b", (HEADKV,)),
    ("*/wv/b", (HEADKV,)),
    ("*/wo/w", (HEADQ, FSDP)),
    ("*/wo/b", (None,)),
    ("*/wi/w", (FSDP, MODEL)),
    ("*/wg/w", (FSDP, MODEL)),
    ("*/wi/b", (MODEL,)),
    ("*/router", (None, None)),
    ("*/w1", (MODEL, FSDP, None)),
    ("*/w3", (MODEL, FSDP, None)),
    ("*/w2", (MODEL, None, FSDP)),
    ("*/in_proj/w", (FSDP, MODEL)),
    ("*/conv_w", (MODEL, None)),
    ("*/conv_b", (MODEL,)),
    ("*/x_proj/w", (MODEL, None)),
    ("*/dt_proj/w", (None, MODEL)),
    ("*/dt_proj/b", (MODEL,)),
    ("*/a_log", (MODEL, None)),
    ("*/d_skip", (MODEL,)),
    ("*/out_proj/w", (MODEL, FSDP)),
    ("*/w_if/w", (MODEL, None)),
    ("*/w_x/w", (FSDP, MODEL)),
    ("*/w_x/b", (MODEL,)),
    ("*/r_h", (None, MODEL, None, None)),
    ("*/ffn_up/w", (FSDP, MODEL)),
    ("*/ffn_down/w", (MODEL, FSDP)),
    ("*/w_dkv/w", (FSDP, None)),
    ("*/w_krope/w", (FSDP, None)),
    ("*/w_uk/w", (None, HEADQ)),
    ("*/w_uv/w", (None, HEADQ)),
    ("*/w_dq/w", (FSDP, None)),
    ("*/w_uq/w", (None, HEADQ)),
]


def _axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    return math.prod(mesh.shape[a] for a in axes)


def leaf_pspec(path: str, shape, mesh: Mesh, policy: ShardingPolicy, cfg=None) -> P:
    """path: 'groups/b0/mixer/wq/w'. Leading 'groups/*' gets a stacked dim."""
    stacked = path.startswith("groups/")
    core_shape = shape[1:] if stacked else shape
    template = None
    for pat, tpl in _RULES:
        if fnmatch.fnmatch(path, pat):
            template = tpl
            break
    msize = mesh.shape[policy.model_axis]
    dims = []
    used: set = set()
    for i, size in enumerate(core_shape):
        ax = template[i] if template and i < len(template) else None
        if not policy.tensor_parallel and ax in (MODEL, HEADQ, HEADKV):
            ax = FSDP  # pure-DP: weights FSDP-shard, never TP
        if ax == FSDP:
            ax = (
                policy.fsdp_axes
                if len(policy.fsdp_axes) > 1
                else (policy.fsdp_axes[0] if policy.fsdp_axes else None)
            )
        elif ax == HEADQ:
            ok = cfg is None or cfg.n_heads % msize == 0
            ax = policy.model_axis if ok else None
        elif ax == HEADKV:
            ok = cfg is None or cfg.n_kv_heads % msize == 0
            ax = policy.model_axis if ok else None
        if ax is not None and size % _axis_size(mesh, ax) != 0:
            ax = None
        # a mesh axis may appear at most once per spec
        flat = (ax,) if (ax is None or isinstance(ax, str)) else tuple(ax)
        if ax is not None and any(a in used for a in flat):
            ax = None
        else:
            used.update(a for a in flat if a)
        dims.append(ax)
    if stacked:
        dims = [None] + dims
    return P(*dims)


def tree_pspecs(tree, mesh: Mesh, policy: ShardingPolicy, cfg=None):
    """Pytree of PartitionSpecs mirroring ``tree`` (of arrays/structs)."""

    def visit(path, leaf):
        names = [getattr(k, "key", getattr(k, "idx", None)) for k in path]
        pstr = "/".join(str(n) for n in names)
        return leaf_pspec(pstr, leaf.shape, mesh, policy, cfg)

    return jax.tree_util.tree_map_with_path(visit, tree)


def tree_shardings(tree, mesh: Mesh, policy: ShardingPolicy, cfg=None):
    return jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec),
        tree_pspecs(tree, mesh, policy, cfg),
        is_leaf=lambda x: isinstance(x, P),
    )


_HOIST_PATTERNS = ("/ffn/wi/", "/ffn/wg/", "/ffn/wo/")


def hoist_constrain(params, mesh: Mesh, policy: ShardingPolicy, cfg=None):
    """Re-constrain dense-FFN weights to their TP-only sharding (FSDP axes
    dropped) so the data-axis all-gather happens once, outside any
    microbatch scan. Other leaves pass through untouched."""
    import dataclasses

    nofsdp = dataclasses.replace(policy, fsdp_axes=())

    def visit(path, leaf):
        names = [getattr(k, "key", getattr(k, "idx", None)) for k in path]
        pstr = "/".join(str(n) for n in names)
        if any(pat in "/" + pstr + "/" for pat in _HOIST_PATTERNS):
            spec = leaf_pspec(pstr, leaf.shape, mesh, nofsdp, cfg)
            return jax.lax.with_sharding_constraint(leaf, NamedSharding(mesh, spec))
        return leaf

    return jax.tree_util.tree_map_with_path(visit, params)


def act_spec(
    policy: ShardingPolicy, mesh: Mesh | None, *, seq_len: int, mode: str
) -> P:
    """Boundary activation spec [B, S, D]."""
    if mesh is None:
        return P()
    batch = tuple(a for a in policy.batch_axes if a in mesh.axis_names)
    b_ax = batch if len(batch) > 1 else (batch[0] if batch else None)
    s_ax = None
    if (
        policy.seq_shard
        and policy.tensor_parallel
        and mode in ("train", "prefill")
        and seq_len % mesh.shape[policy.model_axis] == 0
    ):
        s_ax = policy.model_axis
    return P(b_ax, s_ax, None)


def constrain(x, mesh: Mesh | None, spec: P):
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def cache_pspec(path: str, shape, mesh: Mesh, policy: ShardingPolicy) -> P:
    """KV-cache / recurrent-state sharding.

    [B, S, H, D] k/v: batch->data; heads->model if divisible, else seq->model.
    latent/state tensors: batch->data, widest divisible dim->model.
    """
    batch = tuple(a for a in policy.batch_axes if a in mesh.axis_names)
    b_ax = batch if len(batch) > 1 else (batch[0] if batch else None)
    msize = mesh.shape[policy.model_axis]
    name = path.rsplit("/", 1)[-1]
    stacked = path.startswith("groups/")
    core = shape[1:] if stacked else shape
    if shape and core and core[0] % _axis_size(mesh, b_ax or ()) != 0:
        b_ax = None
    dims = [b_ax] + [None] * (len(core) - 1)
    if name in ("k", "v"):
        if core[2] % msize == 0:
            dims[2] = policy.model_axis
        elif core[1] % msize == 0:
            dims[1] = policy.model_axis
    elif name in ("ckv", "krope", "kpos"):
        if name != "kpos" and len(core) > 2 and core[1] % msize == 0:
            dims[1] = policy.model_axis
    elif name in ("ssm", "conv", "c", "n", "m", "h"):
        # shard the channel/head dim over model when divisible
        for i in range(1, len(core)):
            if core[i] % msize == 0 and core[i] >= msize:
                dims[i] = policy.model_axis
                break
    if stacked:
        dims = [None] + dims
    return P(*dims)


def cache_shardings(tree, mesh: Mesh, policy: ShardingPolicy):
    def visit(path, leaf):
        names = [getattr(k, "key", getattr(k, "idx", None)) for k in path]
        pstr = "/".join(str(n) for n in names)
        return NamedSharding(mesh, cache_pspec(pstr, leaf.shape, mesh, policy))

    return jax.tree_util.tree_map_with_path(visit, tree)


# ---------------------------------------------------------------------------
# Fleet-axis sharding (population parallelism for the fleet engine)
# ---------------------------------------------------------------------------
def make_fleet_mesh(n_devices: int | None = None, *, axis: str = FLEET) -> Mesh | None:
    """A 1-D device mesh for the fleet's stacked agent axis.

    ``n_devices`` is how many local devices join. ``None``/``-1`` means
    all of them, rounded *down* to a power of two so the engine's pow2
    slot buckets always divide the mesh. An explicit count must be a
    power of two that the visible devices cover; anything else raises —
    a mesh never comes out smaller than asked. Returns ``None`` when one
    device would participate — callers treat that as "stay on the
    single-device path", so ``make_fleet_mesh()`` is safe to call
    unconditionally on CPU CI.
    """
    devices = jax.devices()
    if n_devices is None or n_devices < 0:
        n = 1 << (len(devices).bit_length() - 1)  # pow2 floor
    else:
        n = n_devices
        if n & (n - 1) or n > len(devices):
            raise ValueError(
                f"a fleet mesh of {n} devices needs a power of two no larger "
                f"than the {len(devices)} visible device(s)"
            )
    if n <= 1:
        return None
    return Mesh(np.array(devices[:n]), (axis,))


@dataclass(frozen=True)
class FleetSharding:
    """Shardings of the fleet chunk's operands on a 1-D agent-axis mesh.

    The per-agent math is embarrassingly parallel, so the whole policy is
    one rule: shard the leading (agent) axis, replicate everything else.
    ``stacked`` covers every :class:`~repro.rl.fleet.FleetState` leaf and
    any ``[N, ...]`` act operand; ``indices`` is the ``[K, N, B]`` replay
    index tensor (agent axis second); ``replicated`` is the shared replay
    pool (every device reads all rows its slots may sample).
    """

    mesh: Mesh
    axis: str = FLEET

    @property
    def stacked(self) -> NamedSharding:
        return NamedSharding(self.mesh, P(self.axis))

    @property
    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    @property
    def indices(self) -> NamedSharding:
        return NamedSharding(self.mesh, P(None, self.axis))

    @property
    def n_devices(self) -> int:
        return self.mesh.size

    def place(self, tree):
        """Commit a stacked pytree (leading agent axis) onto the mesh."""
        return jax.tree_util.tree_map(
            lambda x: jax.device_put(x, self.stacked), tree
        )
