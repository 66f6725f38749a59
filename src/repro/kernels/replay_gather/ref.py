"""Pure-jnp oracle for replay_gather."""

from __future__ import annotations


def replay_gather_ref(buffer, indices, weights):
    return buffer[indices] * weights.astype(buffer.dtype)[:, None, None]
