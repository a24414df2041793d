"""Shared plumbing for the decentralized bilevel algorithms.

Conventions
-----------
* Every per-node quantity (parameters X/Y, estimators U/V, trackers Z) is a
  pytree whose leaves carry a **leading node axis K**.
* A step batch is ``{'f': ξ, 'g': ζ0, 'h': ζ_{1..J}}`` where leaves of 'f'/'g'
  have leading axis K and leaves of 'h' have leading axes (K, J).
* Per-node randomness (the Neumann truncation level J̃) comes from a key vector
  of shape (K,).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.core.hypergrad import HypergradConfig, stochastic_hypergrad
from repro.core.problems import BilevelProblem

Batch = Any
Tree = Any


@dataclasses.dataclass(frozen=True)
class HParams:
    """Step sizes of Algorithms 1/2. ``eta``∈(0,1); momentum factors are
    α1·η / α2·η for MDBO (Eq. 7) and α1·η² / α2·η² for VRDBO (Eq. 10)."""

    eta: float = 0.1
    alpha1: float = 1.0
    alpha2: float = 1.0
    beta1: float = 1.0
    beta2: float = 1.0


def node_grads(problem: BilevelProblem, cfg: HypergradConfig,
               X: Tree, Y: Tree, batch: Batch, keys: jax.Array):
    """Per-node (Δ^F̃, Δ^g): stochastic hypergradient wrt x and ∇_y g, vmapped
    over the node axis. All Hessian/Jacobian work stays inside the node."""

    def one(x, y, fb, gb, hb, key):
        hg = stochastic_hypergrad(problem, cfg, x, y, fb, gb, hb, key)
        gy = jax.grad(problem.lower_loss, argnums=1)(x, y, gb)
        return hg, gy

    return jax.vmap(one)(X, Y, batch["f"], batch["g"], batch["h"], keys)


def consensus_error(tree: Tree) -> jax.Array:
    """(1/K)‖A − Ā‖_F² over all leaves (the paper's consensus diagnostic)."""
    def leaf(a):
        mean = jnp.mean(a, axis=0, keepdims=True)
        return jnp.sum((a - mean) ** 2) / a.shape[0]
    return jax.tree.reduce(jnp.add, jax.tree.map(leaf, tree))


def node_mean(tree: Tree) -> Tree:
    return jax.tree.map(lambda a: jnp.mean(a, axis=0), tree)


def replicate(tree: Tree, K: int, sharding=None) -> Tree:
    """Stack K identical copies (the paper's x_0^{(k)} = x_0 initialisation).

    ``sharding`` (e.g. a ``NamedSharding`` over the node axis of a mesh)
    places every stacked leaf at creation time, so mesh runs start node-
    sharded instead of being resharded at the first jit boundary."""
    def stack(t):
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (K,) + a.shape), t)

    if sharding is None:
        return stack(tree)
    # built in place: no device ever holds all K copies
    return jax.jit(stack, out_shardings=sharding)(tree)
