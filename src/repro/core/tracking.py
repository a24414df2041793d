"""Gradient-tracking + gossip mixing operators.

Two implementations of the communication primitive ``A ↦ A W`` (A stacked over
nodes on the leading axis):

* :func:`dense_mix` — paper-faithful einsum with the K×K mixing matrix. Under
  pjit with the node axis sharded, XLA lowers this to an all-gather + local
  contraction.
* :func:`ring_mix` — exact rewrite for the ring topology: every node only needs
  its two neighbors, i.e. two ``collective_permute`` ops on a TPU ICI ring plus
  a 3-term weighted sum. Same numerics as ``dense_mix(ring W)`` (tested), but
  collective bytes drop from O(K·d) (gather) to 2·d per mix. This is the
  beyond-paper TPU-native optimization recorded in EXPERIMENTS.md §Perf.

The gradient-tracking recursion (Eq. 8):   Z_t = Z_{t−1} W + U_t − U_{t−1}.
Its defining invariant, mean_k Z_t^{(k)} = mean_k U_t^{(k)}, is property-tested.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.hypergrad import tree_add, tree_sub

MixFn = Callable[[object], object]


def dense_mix(W) -> MixFn:
    """A ↦ W A (out[i] = Σ_j W[i,j] A[j]) on every pytree leaf, leading axis=K."""
    Wj = jnp.asarray(W)

    def mix(tree):
        def leaf(a):
            return jnp.tensordot(Wj, a, axes=([1], [0])).astype(a.dtype)
        return jax.tree.map(leaf, tree)

    return mix


def ring_mix_local(axis_name: str, self_weight: float = 1.0 / 3.0,
                   size: int | None = None) -> MixFn:
    """Ring mixing *inside* shard_map: node axis is the mesh axis ``axis_name``
    and each shard holds a single node's slice (leading axis length 1 or the
    raw per-node tree). Uses two collective_permutes (left/right neighbor).
    ``size`` pins the ring length; left None it is read off the axis env."""
    nb = (1.0 - self_weight) / 2.0

    def mix(tree):
        n = jax.lax.axis_size(axis_name) if size is None else size
        left = [(i, (i - 1) % n) for i in range(n)]
        right = [(i, (i + 1) % n) for i in range(n)]

        def leaf(a):
            a_from_right = jax.lax.ppermute(a, axis_name, left)
            a_from_left = jax.lax.ppermute(a, axis_name, right)
            return (self_weight * a + nb * a_from_left + nb * a_from_right
                    ).astype(a.dtype)

        return jax.tree.map(leaf, tree)

    return mix


def ring_mix_rolled(self_weight: float = 1.0 / 3.0) -> MixFn:
    """Single-process ring mixing via jnp.roll on the leading node axis.

    Equivalent to dense_mix(ring(K).weights) without materializing W; inside
    pjit the rolls lower to collective_permute when the axis is sharded."""
    nb = (1.0 - self_weight) / 2.0

    def mix(tree):
        def leaf(a):
            K = a.shape[0]
            if K == 1:
                return a
            if K == 2:
                return (0.5 * a + 0.5 * jnp.roll(a, 1, axis=0)).astype(a.dtype)
            return (self_weight * a + nb * jnp.roll(a, 1, axis=0)
                    + nb * jnp.roll(a, -1, axis=0)).astype(a.dtype)
        return jax.tree.map(leaf, tree)

    return mix


def track_update(z_prev, u_new, u_prev, mix: MixFn):
    """Z_t = mix(Z_{t−1}) + U_t − U_{t−1}  (Eq. 8)."""
    return tree_add(mix(z_prev), tree_sub(u_new, u_prev))


def param_update(x, z, eta: float, beta: float, mix: MixFn):
    """X_{t+1} = X_t − η X_t (I − W) − β η Z_t  (Eq. 9)
              = (1−η) X_t + η mix(X_t) − β η Z_t."""
    mixed = mix(x)
    return jax.tree.map(
        lambda xx, mm, zz: (1.0 - eta) * xx + eta * mm - beta * eta * zz,
        x, mixed, z)


def gossip_param_update(x, d, lr: float, mix: MixFn):
    """Baseline gossip update: X_{t+1} = mix(X_t) − lr · D_t."""
    mixed = mix(x)
    return jax.tree.map(lambda mm, dd: mm - lr * dd, mixed, d)
