"""Plain reference of the 3-D DQN agent, its TD loss and its optimizer.

Written from the architecture's description alone; it imports nothing of
the program under test. The network: stride-2, kernel-3, SAME 3-D
convolutions with ReLU over a one-channel crop, a 16-unit ReLU branch over
the normalized location, both flattened and concatenated, then fully
connected layers (ReLU between them) to one Q-value per action. Training:
the mean Huber TD loss against a target network's greedy value, Adam with
global-norm clipping at 10 (no weight decay, constant rate), and a hard
target sync every ``target_update`` steps of the agent's own counter.

Precision modes (``MODES``): ``highest`` is the reference, float32 at
``Precision.HIGHEST``. ``default`` is float32 at XLA's default precision,
the arithmetic the configuration states for the program (one bfloat16
pass with float32 accumulation on the TPU): a second witness. ``fp8`` is
the control, the step below it: every convolution's and dense layer's
operands rounded to float8 (e4m3, one scale per tensor), float32
accumulation, gradients and storage. ``bf16`` keeps every array and operation in
bfloat16 (the storage step below float32), for the readings only.

The network reads one crop (``obs_shape``). The replay rows are the flat
layout the benchmark reads from the device pool (``row_fields``): obs,
loc, action, reward, next_obs, next_loc, done, then zero lanes. The
benchmark's driver shapes its warm-up and reads the pool's rows by these
two; ``macs_per_sample`` and ``row_floats`` count this network's
operations and this row's floats for its FLOP and byte counters
(``harness/flops.py``).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
MODES = ("highest", "default", "fp8", "bf16")
E4M3_MAX = 448.0
ADAM = {"b1": 0.9, "b2": 0.95, "eps": 1e-8, "clip": 10.0}


def conv_dims(box, n_conv):
    dims = list(box)
    for _ in range(n_conv):
        dims = [-(-d // 2) for d in dims]
    return dims


def init_params(seed: int, cfg: dict):
    """The fan-in scaled truncated-normal initialization (zero biases),
    drawn from ``PRNGKey(seed)`` in a fixed order of subkeys: convolution
    ``i`` from subkey ``i`` of an 8-way split, the location branch from
    subkey 5, fully connected layer ``i`` from subkey 6 folded with ``i``."""
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 8)

    def tn(k, shape, scale):
        return scale * jax.random.truncated_normal(k, -2.0, 2.0, shape, jnp.float32)

    p = {}
    cin = 1
    for i, cout in enumerate(cfg["conv_features"]):
        p[f"conv{i}"] = {
            "w": tn(ks[i], (3, 3, 3, cin, cout), (cin * 27) ** -0.5),
            "b": jnp.zeros((cout,), jnp.float32),
        }
        cin = cout
    dims = conv_dims(obs_shape(cfg), len(cfg["conv_features"]))
    d = dims[0] * dims[1] * dims[2] * cin + 16
    p["loc"] = {"w": tn(ks[5], (3, 16), 3**-0.5), "b": jnp.zeros((16,), jnp.float32)}
    for i, h in enumerate(list(cfg["hidden"]) + [cfg["n_actions"]]):
        p[f"fc{i}"] = {
            "w": tn(jax.random.fold_in(ks[6], i), (d, h), d**-0.5),
            "b": jnp.zeros((h,), jnp.float32),
        }
        d = h
    return p


def _dtype(mode: str):
    return jnp.bfloat16 if mode == "bf16" else jnp.float32


def _operand(x, mode: str):
    """A matmul operand as the mode computes with it: in ``fp8`` rounded
    to float8 e4m3 under one scale per tensor (its largest magnitude maps
    to e4m3's largest), the gradient passed straight through in float32;
    otherwise as it is."""
    if mode != "fp8":
        return x
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale
    return x + jax.lax.stop_gradient(q - x)


def forward(cfg: dict, p, obs, loc, mode: str = "highest"):
    """obs [B, bx, by, bz], loc [B, 3] -> Q [B, n_actions]."""
    prec = HIGHEST if mode == "highest" else None
    dtype = _dtype(mode)
    x = obs.astype(dtype)[..., None]
    for i in range(len(cfg["conv_features"])):
        w, b = p[f"conv{i}"]["w"].astype(dtype), p[f"conv{i}"]["b"].astype(dtype)
        x = jax.lax.conv_general_dilated(
            _operand(x, mode),
            _operand(w, mode),
            window_strides=(2, 2, 2),
            padding="SAME",
            dimension_numbers=("NDHWC", "DHWIO", "NDHWC"),
            precision=prec,
        )
        x = jnp.maximum(x + b, 0)
    x = x.reshape(x.shape[0], -1)
    lw, lb = p["loc"]["w"].astype(dtype), p["loc"]["b"].astype(dtype)
    lh = jnp.maximum(jnp.dot(_operand(loc.astype(dtype), mode), _operand(lw, mode), precision=prec) + lb, 0)
    x = jnp.concatenate([x, lh], -1)
    n_fc = len(cfg["hidden"]) + 1
    for i in range(n_fc):
        w, b = p[f"fc{i}"]["w"].astype(dtype), p[f"fc{i}"]["b"].astype(dtype)
        x = jnp.dot(_operand(x, mode), _operand(w, mode), precision=prec) + b
        if i < n_fc - 1:
            x = jnp.maximum(x, 0)
    return x


def obs_shape(cfg: dict) -> tuple[int, ...]:
    """The shape of one observation as the network reads it: one crop."""
    return tuple(cfg["box_size"])


def row_fields(cfg: dict) -> list[tuple[str, int]]:
    """The flat replay row's fields and their widths, in order."""
    f = int(np.prod(obs_shape(cfg)))
    return [("obs", f), ("loc", 3), ("action", 1), ("reward", 1), ("next_obs", f), ("next_loc", 3), ("done", 1)]


def row_floats(cfg: dict) -> int:
    """Floats of one replay row: obs and next obs, loc and next loc,
    action, reward, done."""
    return sum(width for _, width in row_fields(cfg))


def macs_per_sample(cfg: dict) -> int:
    """Multiply-adds of one forward pass over one crop, by the dense count:
    a convolution costs ``out_voxels * 27 * c_in * c_out``, taps that land
    on the zero padding included (XLA's cost analysis skips those, so it
    reads lower); the location branch ``3 * 16``; a dense layer
    ``d_in * d_out``."""
    dims = list(obs_shape(cfg))
    cin = 1
    total = 3 * 16
    for cout in cfg["conv_features"]:
        dims = conv_dims(dims, 1)
        total += int(np.prod(dims)) * 27 * cin * cout
        cin = cout
    d = int(np.prod(dims)) * cin + 16
    for h in list(cfg["hidden"]) + [cfg["n_actions"]]:
        total += d * h
        d = h
    return total


def split_rows(cfg: dict, rows):
    """[B, W] flat replay rows -> batch dict."""
    box = obs_shape(cfg)
    b = rows.shape[0]
    o = 0
    out = {}
    for key, width in row_fields(cfg):
        out[key] = rows[:, o : o + width]
        o += width
    out["obs"] = out["obs"].reshape(b, *box)
    out["next_obs"] = out["next_obs"].reshape(b, *box)
    for key in ("action", "reward", "done"):
        out[key] = out[key][:, 0]
    out["action"] = out["action"].astype(jnp.int32)
    return out


def td_loss(cfg: dict, params, target, batch, mode: str = "highest"):
    q = forward(cfg, params, batch["obs"], batch["loc"], mode)
    q_sel = jnp.take_along_axis(q, batch["action"][:, None], 1)[:, 0]
    q_next = forward(cfg, target, batch["next_obs"], batch["next_loc"], mode)
    best = jax.lax.stop_gradient(jnp.max(q_next, -1))
    reward = batch["reward"].astype(q.dtype)
    done = batch["done"].astype(q.dtype)
    delta = q_sel - (reward + cfg["gamma"] * (1 - done) * best)
    absd = jnp.abs(delta)
    return jnp.mean(jnp.where(absd <= 1, 0.5 * delta * delta, absd - 0.5))


def _adam(cfg: dict, params, grads, m, v, count, dtype):
    count = count + 1
    leaves = jax.tree_util.tree_leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
    scale = jnp.minimum(1.0, ADAM["clip"] / jnp.maximum(gnorm, 1e-9)).astype(dtype)
    c = count.astype(dtype)
    bc1 = 1 - ADAM["b1"] ** c
    bc2 = 1 - ADAM["b2"] ** c
    lr = cfg["lr"]

    def upd(p, g, mm, vv):
        g = g * scale
        mm = ADAM["b1"] * mm + (1 - ADAM["b1"]) * g
        vv = ADAM["b2"] * vv + (1 - ADAM["b2"]) * g * g
        step = (mm / bc1) / (jnp.sqrt(vv / bc2) + ADAM["eps"])
        return (p - lr * step).astype(dtype), mm.astype(dtype), vv.astype(dtype)

    out = jax.tree_util.tree_map(upd, params, grads, m, v)
    tdef = jax.tree_util.tree_structure(params)
    new_p, new_m, new_v = (
        jax.tree_util.tree_unflatten(tdef, [o[i] for o in tdef.flatten_up_to(out)])
        for i in range(3)
    )
    return new_p, new_m, new_v, count


@partial(jax.jit, static_argnames=("cfg_items", "mode"))
def _train(cfg_items, mode, params, pool, idx, count0):
    """One agent's K steps from fresh Adam state: returns per-step losses,
    the first step's per-leaf gradient norms and, after each step, the
    norm of each leaf's change from the start ([K, leaves])."""
    cfg = dict(cfg_items)
    dtype = _dtype(mode)
    start = jax.tree_util.tree_map(lambda x: x.astype(dtype), params)
    zeros = jax.tree_util.tree_map(jnp.zeros_like, start)

    def norms(tree):
        return jnp.stack(
            [jnp.sqrt(jnp.sum(x.astype(jnp.float32) ** 2)) for x in jax.tree_util.tree_leaves(tree)]
        )

    def body(carry, idx_k):
        p, t, m, v, n_adam, count = carry
        batch = split_rows(cfg, pool[idx_k].astype(dtype))
        loss, g = jax.value_and_grad(lambda pp: td_loss(cfg, pp, t, batch, mode))(p)
        gnorms = norms(g)
        p, m, v, n_adam = _adam(cfg, p, g, m, v, n_adam, dtype)
        count = count + 1
        sync = count % cfg["target_update"] == 0
        t = jax.tree_util.tree_map(lambda a, b: jnp.where(sync, b, a), t, p)
        moved = norms(jax.tree_util.tree_map(lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), p, params))
        return (p, t, m, v, n_adam, count), (loss.astype(jnp.float32), gnorms, moved)

    carry = (start, start, zeros, zeros, jnp.zeros((), jnp.int32), count0)
    _, (losses, gnorms, moved) = jax.lax.scan(body, carry, idx)
    return losses, gnorms[0], moved


def _hashable(cfg: dict):
    keys = ("box_size", "conv_features", "hidden", "n_actions", "gamma", "lr", "target_update")
    return tuple((k, tuple(cfg[k]) if isinstance(cfg[k], list) else cfg[k]) for k in keys)


def train(cfg: dict, params, pool, idx, count0=0, mode: str = "highest"):
    """Follow one agent through ``idx.shape[0]`` steps over ``pool``
    ([R, W] rows; ``idx`` [K, B]). Returns host arrays: losses [K], the
    first gradient's per-leaf norms [L], and each leaf's change norm after
    each step [K, L]; leaves in sorted key order."""
    with jax.default_matmul_precision("highest" if mode == "highest" else "default"):
        losses, g1, moved = _train(
            _hashable(cfg), mode, params, jnp.asarray(pool), jnp.asarray(idx), jnp.int32(count0)
        )
    return np.asarray(losses), np.asarray(g1), np.asarray(moved)


@partial(jax.jit, static_argnames=("cfg_items", "mode"))
def _qvals(cfg_items, mode, params, obs, loc):
    return forward(dict(cfg_items), params, obs, loc, mode).astype(jnp.float32)


def qvals(cfg: dict, params, obs, loc, mode: str = "highest"):
    """Q-values [B, n_actions] as a host array, in blocks of 64 rows."""
    out = []
    with jax.default_matmul_precision("highest" if mode == "highest" else "default"):
        for s in range(0, len(obs), 64):
            out.append(
                np.asarray(
                    _qvals(_hashable(cfg), mode, params, jnp.asarray(obs[s : s + 64]), jnp.asarray(loc[s : s + 64]))
                )
            )
    return np.concatenate(out)
