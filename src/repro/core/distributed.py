"""True multi-device execution of the decentralized algorithms via shard_map.

The simulator (core.driver) stacks nodes on a leading axis of one array; here
each mesh shard *owns* its node and the ring gossip is two physical
``collective_permute``s (the engine's ``ring_local`` mix backend). The
algorithm bodies are reused unchanged through the engine's algorithm registry
(mdbo.step / vrdbo.step are pure in the mix operator).

For scan-fused multi-step execution over a mesh, build an
:class:`repro.core.engine.Engine` with ``mix="ring_local"`` directly — these
helpers remain the minimal per-call entry points.

Numerical note: dense_mix(ring(K).weights) and the ppermute ring mix are the
same matrix product evaluated in different orders; equivalence is tested to
float32 tolerance in tests/test_distributed.py (subprocess with forced host
devices).
"""
from __future__ import annotations

from functools import partial
from typing import Any

import jax
from jax.sharding import PartitionSpec as P

from repro.core.common import HParams
from repro.core.engine import ALGORITHMS, make_mix
from repro.core.hypergrad import HypergradConfig
from repro.core.problems import BilevelProblem

Tree = Any


def make_distributed_step(problem: BilevelProblem, hcfg: HypergradConfig,
                          hp: HParams, mesh, *, algo: str = "mdbo",
                          axis_name: str = "data",
                          self_weight: float = 1.0 / 3.0):
    """jit-able step over ``mesh``: node k lives on shard k of ``axis_name``;
    gossip = 2 collective_permutes. State/batch/keys keep the leading node
    axis (length K = mesh.shape[axis_name]), sharded 1-per-device."""
    mix = make_mix("ring_local", K=mesh.shape[axis_name], axis_name=axis_name,
                   self_weight=self_weight)
    inner = partial(ALGORITHMS[algo].step, problem, hcfg, hp, mix)

    spec = P(axis_name)  # prefix pytree: every leaf node-sharded on dim 0

    def step(state, batch, keys):
        return jax.shard_map(inner, mesh=mesh, in_specs=(spec,) * 3,
                             out_specs=spec, check_vma=False)(
            state, batch, keys)

    return jax.jit(step)


def make_distributed_init(problem: BilevelProblem, hcfg: HypergradConfig,
                          hp: HParams, mesh, *, algo: str = "mdbo",
                          axis_name: str = "data",
                          self_weight: float = 1.0 / 3.0):
    mix = make_mix("ring_local", K=mesh.shape[axis_name], axis_name=axis_name,
                   self_weight=self_weight)
    inner = partial(ALGORITHMS[algo].init, problem, hcfg, hp, mix)

    spec = P(axis_name)

    def init(X0, Y0, batch, keys):
        return jax.shard_map(inner, mesh=mesh, in_specs=(spec,) * 4,
                             out_specs=spec, check_vma=False)(
            X0, Y0, batch, keys)

    return jax.jit(init)
