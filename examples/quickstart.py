"""Quickstart: the three layers of the framework in ~60 seconds on CPU.

1. model zoo   — one reduced config, one train step, one decode step
2. ADFLL core  — two agents share experience through a hub
3. kernels     — fused flash-attention vs its oracle

    PYTHONPATH=src python examples/quickstart.py
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.adfll_dqn import DQNConfig
from repro.configs.base import get_config
from repro.core.erb import TaskTag
from repro.core.hub import Hub
from repro.core.network import Network
from repro.models.model import build_model, init_caches
from repro.rl.agent import DQNAgent
from repro.rl.env import LandmarkEnv
from repro.rl.synth import make_volume

# ---------------------------------------------------------------- 1. zoo
cfg = get_config("qwen3-moe-235b-a22b-smoke")  # reduced MoE variant
model = build_model(cfg)
state = model.init_train_state(jax.random.PRNGKey(0))
rng = np.random.default_rng(0)
batch = {
    "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 64)), jnp.int32),
    "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 64)), jnp.int32),
}
state, metrics = jax.jit(model.train_step)(state, batch)
print(
    f"[zoo] {cfg.name}: loss={float(metrics['loss']):.3f} "
    f"aux={float(metrics['aux']):.3f}"
)
caches = init_caches(cfg, 2, 16)
logits, caches = jax.jit(model.serve_step)(
    state["params"],
    caches,
    {"tokens": jnp.zeros((2, 1), jnp.int32), "pos": jnp.zeros((2,), jnp.int32)},
)
print(f"[zoo] decode logits {logits.shape}")

# ------------------------------------------------------------- 2. ADFLL
dqn = DQNConfig(
    volume_shape=(16, 16, 16),
    box_size=(6, 6, 6),
    conv_features=(4,),
    hidden=(32,),
    max_episode_steps=12,
    batch_size=16,
)
task_a = TaskTag("t1", "axial", "HGG")
task_b = TaskTag("t2", "coronal", "LGG")
net = Network(hubs=[Hub(0)])
net.attach_agent(0)
net.attach_agent(1)
a0 = DQNAgent(0, dqn, seed=0)
a1 = DQNAgent(1, dqn, seed=1)
vol, lm = make_volume(task_a, 0, n=16)
shared, _ = a0.train_round(
    LandmarkEnv(vol, lm, dqn),
    task_a,
    (),
    erb_capacity=512,
    share_size=64,
    train_steps=20,
)
net.agent_push(0, shared)  # A0 -> hub
incoming = net.agent_pull(1, a1.seen_erb_ids)
vol, lm = make_volume(task_b, 1, n=16)
_, loss = a1.train_round(
    LandmarkEnv(vol, lm, dqn),
    task_b,
    incoming,
    erb_capacity=512,
    share_size=64,
    train_steps=20,
)
print(
    f"[adfll] agent1 trained on its task + {len(incoming)} foreign "
    f"ERB(s) from the hub, loss={loss:.4f}"
)

# -------------------------------------------------- 2b. weight plane
# Beyond the paper: the same hub can also carry FedAsync-style parameter
# snapshots, mixed with staleness-discounted rates alpha * s(dtau).
from repro.core.plane import WeightPlane, staleness_alphas

net.register_plane(WeightPlane(max_versions=2))
net.agent_push(0, a0.snapshot_params(sim_time=1.0), plane="weights")
snaps = net.agent_pull(1, a1.seen_snap_ids, plane="weights")
alphas = staleness_alphas(snaps, a1.rounds_done, alpha=0.5, flag="poly")
n = a1.mix_params(snaps, alphas)
print(
    f"[adfll] agent1 mixed {n} peer weight snapshot(s), "
    f"alpha={[round(float(a), 3) for a in alphas]}"
)

# ------------------------------------------------------------ 3. kernels
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref

q = jnp.asarray(rng.standard_normal((1, 128, 4, 32)), jnp.float32)
k = jnp.asarray(rng.standard_normal((1, 128, 2, 32)), jnp.float32)
v = jnp.asarray(rng.standard_normal((1, 128, 2, 32)), jnp.float32)
out = flash_attention(q, k, v, block_q=64, block_k=64, interpret=True)
err = float(jnp.abs(out - attention_ref(q, k, v)).max())
print(f"[kernels] flash attention (interpret) max err vs oracle: {err:.2e}")
print("done.")
