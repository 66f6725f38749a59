"""3D landmark-localization environment (paper Appendix A.1).

Agent = 3D bounding box; 6 actions (+/- x, y, z); reward = decrease in
Euclidean distance to the target landmark; episode terminates on
proximity or step budget. Vectorized over parallel episodes (numpy host
side; the Q-network forward is the jitted part).
"""

from __future__ import annotations

from collections.abc import Sequence
from dataclasses import dataclass, field

import numpy as np

from repro.configs.adfll_dqn import DQNConfig

# actions: 0:+x 1:-x 2:+y 3:-y 4:+z 5:-z  (acting on [z,y,x] index order)
_DELTA = np.array(
    [[0, 0, 1], [0, 0, -1], [0, 1, 0], [0, -1, 0], [1, 0, 0], [-1, 0, 0]], np.int32
)


def apply_actions(
    locs: np.ndarray, actions: np.ndarray, n, step_size: int
) -> np.ndarray:
    """Move ``locs`` [B,3] by ``actions`` [B] and clip to the volume.

    The landmark-free half of :meth:`LandmarkEnv.step` — the serving
    plane moves requests through volumes whose landmark it does not
    know, so the kinematics must not require one. ``n`` is the volume
    side: a scalar, or [B] per-row sides when the batch mixes volumes.
    """
    hi = np.asarray(n, np.int32) - 1
    if hi.ndim:
        hi = hi[:, None]
    return np.clip(locs + step_size * _DELTA[actions], 0, hi).astype(np.int32)


def observe_many(
    envs: Sequence["LandmarkEnv"], locs: np.ndarray
) -> tuple[np.ndarray, np.ndarray]:
    """Per-request observation batch over *heterogeneous* environments.

    ``envs[i]`` supplies row ``i``'s crop and normalized location —
    unlike :meth:`LandmarkEnv.observe`, which batches many locations in
    *one* volume. Returns ``(obs [B, box], norm_loc [B, 3])``; this is
    the host half of a serving tick (each request owns its own volume).
    """
    obs = np.stack(
        [env.observe(loc[None])[0] for env, loc in zip(envs, locs, strict=True)]
    )
    norm = np.stack(
        [env.norm_loc(loc) for env, loc in zip(envs, locs, strict=True)]
    ).astype(np.float32)
    return obs, norm


@dataclass
class LandmarkEnv:
    volume: np.ndarray  # [n,n,n] f32
    landmark: np.ndarray  # [3] float (zyx)
    cfg: DQNConfig
    # pad-once cache: np.pad of the full volume on *every* observe call
    # would cost more than the crops themselves
    _padded: np.ndarray | None = field(default=None, repr=False, compare=False)

    @property
    def n(self) -> int:
        return self.volume.shape[0]

    def observe(self, locs: np.ndarray, out: np.ndarray | None = None) -> np.ndarray:
        """locs [B,3] int -> crops [B, bx,by,bz] float32 centered at locs
        (zero-padded at boundaries), written into ``out`` when given.

        Each window is one basic slice of a cached zero-padded volume,
        copied row by row: a broadcast fancy index over the same windows
        costs about ten times as much on the host. A window that leaves
        the padded volume raises ``IndexError``."""
        box = self.cfg.box_size
        pad = max(box)
        if self._padded is None:
            self._padded = np.pad(self.volume, pad)
        starts = np.asarray(locs) + pad - np.asarray(box) // 2  # [B,3]
        if (starts < 0).any() or (starts + box > self._padded.shape).any():
            raise IndexError(f"crop window outside the padded volume at {locs}")
        if out is None:
            out = np.empty((len(starts), *box), np.float32)
        bx, by, bz = box
        for row, (z, y, x) in zip(out, starts.tolist(), strict=True):
            row[...] = self._padded[z : z + bx, y : y + by, x : x + bz]
        return out

    def norm_loc(self, locs: np.ndarray) -> np.ndarray:
        return locs.astype(np.float32) / (self.n - 1)

    def dist(self, locs: np.ndarray) -> np.ndarray:
        return np.linalg.norm(locs.astype(np.float32) - self.landmark, axis=-1)

    def start_locs(self, batch: int, rng: np.random.Generator) -> np.ndarray:
        lo, hi = self.n // 4, 3 * self.n // 4
        return rng.integers(lo, hi, size=(batch, 3)).astype(np.int32)

    def step(
        self, locs: np.ndarray, actions: np.ndarray
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """-> (new_locs, reward, done)."""
        new = apply_actions(locs, actions, self.n, self.cfg.step_size)
        r = self.dist(locs) - self.dist(new)
        done = self.dist(new) < 1.5
        return new, r.astype(np.float32), done
