"""Scan-fused execution engine — the single run substrate of the repo.

Every run path (driver simulator, shard_map distributed, the decentralized LM
trainer, benchmarks, examples) drives the same :class:`Engine`:

* **Dispatch** — ``fused`` compiles a whole eval interval (``eval_every``
  steps) into ONE device program via :func:`jax.lax.scan`: state buffers are
  donated between chunks and cheap consensus diagnostics are accumulated
  in-scan, so the host touches the device once per interval instead of once
  per step. ``per_step`` keeps the legacy one-jit-call-per-iteration loop
  (the dispatch-overhead baseline measured in ``benchmarks/engine_bench.py``
  and ``benchmarks/trainer_bench.py``).
* **Mix backends** — a registry of the communication primitive ``A ↦ W A``
  selected by name: ``dense`` (einsum with the K×K mixing matrix),
  ``ring_rolled`` (jnp.roll, W-free), ``ring_local`` (shard_map +
  collective_permute; one node per mesh shard; ``mix_kwargs=
  {'error_feedback': True, 'ratio': r}`` runs EF21-compressed gossip with
  shard-local accumulators), the compressed-gossip operators
  ``compressed_topk`` / ``compressed_rand`` (A + (W−I)·C(A); keep fraction
  via ``mix_kwargs={'ratio': ...}``, EF21 via
  ``mix_kwargs={'error_feedback': True}``), and ``async_gossip``
  (stale-by-τ ring gossip: double-buffered neighbor caches refreshed under a
  per-edge drop model, ``mix_kwargs={'tau': t, 'drop_prob': p}``; τ=0 is
  bitwise synchronous; with a mesh it exchanges via ppermute under
  shard_map). Callers stop hand-rolling their own mix construction.
* **Stateful-mix carry threading** — mixes that carry state between steps
  (EF21 accumulators, async neighbor caches) declare ``stateful = True`` and
  expose ``state0(site_shapes, site_index)`` / ``bind(states)`` /
  ``apply(tree, state)``. The engine discovers the mix call sites of a step
  by trace order (``eval_shape``), seeds one carry slot per site, and
  threads the slots through its scan carry — algorithm bodies stay pure in
  the mix operator and never see the state. Every carry leaf keeps a leading
  node axis K, so shard-local backends shard the mix state with the same
  ``P(axis_name)`` prefix as the algorithm state.
* **Mesh execution** — pass ``mesh`` plus the node-axis name (``data`` for
  per-node parameter copies, ``pod`` for FSDP-inside-a-node pods, per
  ``ArchSpec.train_mode``). ``ring_local`` runs the algorithm body under
  shard_map with the node-stacked state/batches sharded over that axis; any
  other backend runs under GSPMD with the initial state placed node-sharded
  (:func:`repro.core.common.replicate` honors the sharding hint), so XLA
  inserts the collectives.
* **Samplers** — a first-class :class:`Sampler` protocol. Device-resident
  samplers (``device_resident = True``; e.g. ``data.make_device_sampler``,
  ``data.make_device_lm_sampler``) are pure JAX and are sampled *inside* the
  scan — LM batches with ``{'f','g','h'(K,J)}`` structure and modality extras
  flow through fused dispatch with zero host round-trips per interval. Host
  samplers (``device_resident = False`` or the legacy ``host_sampler = True``
  attribute, e.g. :class:`repro.data.NodeSampler`) are drawn per-step on the
  host and stacked on a leading time axis the scan consumes. Bare callables
  are accepted and treated as device-resident.
* **Key discipline** — every iteration consumes two *independent* subkeys,
  one for the minibatch draw and one for the per-node Neumann truncation
  level J̃, via :func:`key_schedule`. (The seed driver reused a single key
  for both, correlating the batch and J̃ streams.)

Bitwise contract (tests/test_engine.py, tests/test_trainer_engine.py,
tests/test_async_gossip.py): a fused run of T steps is bit-identical to T
per-step ``step_fn`` calls under the same key schedule, for every algorithm
and every mix backend; ``async_gossip`` at τ=0 is additionally bit-identical
to synchronous ring gossip.

Module contract: algorithm bodies, mix operators, samplers marked
``device_resident`` and everything threaded through the scan carry are pure
JAX; the only host-side code is the chunk loop in :meth:`Engine.run` (result
recording, ``on_eval`` hooks, host-sampler pre-stacking).
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import baselines, mdbo, vrdbo
from repro.core.common import (HParams, consensus_error, node_mean,
                               replicate)
from repro.core.hypergrad import HypergradConfig, tree_zeros_like
from repro.core.problems import BilevelProblem
from repro.core.topology import Topology, ring
from repro.core.tracking import (MixFn, dense_mix, param_update,
                                 ring_mix_local, ring_mix_rolled,
                                 track_update)

Tree = Any

# ---------------------------------------------------------------------------
# Sampler protocol
# ---------------------------------------------------------------------------

class Sampler:
    """First-class sampler protocol for :meth:`Engine.run`.

    ``sample(key)`` returns a step batch ``{'f','g','h'}`` with node axis K
    (J axis on 'h'); modality extras ride along as extra dict entries.
    ``device_resident`` declares whether ``sample`` is pure JAX — traced into
    the fused scan so a whole eval interval is one device program — or host
    code, drawn per-step and stacked on a leading time axis.

    Bare callables are also accepted by the engine: device-resident by
    default, host-side if they carry the legacy ``host_sampler = True``.
    """

    device_resident: bool = True

    def sample(self, key):
        raise NotImplementedError

    def __call__(self, key=None):
        return self.sample(key)


class DeviceSampler(Sampler):
    """Wrap a pure-JAX ``sample(key) -> batch`` function as a Sampler."""

    def __init__(self, fn: Callable):
        self._fn = fn

    def sample(self, key):
        return self._fn(key)


def is_host_sampler(sample_batch) -> bool:
    """Host vs device-resident, honoring the legacy ``host_sampler`` attr."""
    resident = getattr(sample_batch, "device_resident", None)
    if resident is not None:
        return not resident
    return bool(getattr(sample_batch, "host_sampler", False))


# ---------------------------------------------------------------------------
# Algorithm registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Algorithm:
    """Uniform signature pair:
    init(problem, cfg, hp, mix, X0, Y0, batch, keys) -> state
    step(problem, cfg, hp, mix, state, batch, keys) -> state
    """

    init: Callable
    step: Callable


def _dsbo_init(problem, cfg, hp, mix, X0, Y0, batch, keys):
    return baselines.dsbo_init(X0, Y0)


def _gt_sgd_grads(problem, X, Y, batch):
    """Per-node ∇_y of the raw (upper) loss on the training draw ζ0."""
    return jax.vmap(lambda x, y, b: jax.grad(
        lambda yy: problem.upper_loss(x, yy, b))(y))(X, Y, batch["g"])


def _gt_sgd_init(problem, cfg, hp, mix, X0, Y0, batch, keys):
    """Single-level gradient-tracking SGD ablation: the upper level is inert
    (x frozen at X0, its estimator/tracker slots zero — not copies of X0, or
    diagnostics that read estimator norms report parameter magnitudes)."""
    dg = _gt_sgd_grads(problem, X0, Y0, batch)
    y1 = param_update(Y0, dg, hp.eta, hp.beta2, mix)
    return mdbo.MDBOState(x=X0, y=y1, u=tree_zeros_like(X0), v=dg,
                          zf=tree_zeros_like(X0), zg=dg)


def _gt_sgd_step(problem, cfg, hp, mix, state, batch, keys):
    dg = _gt_sgd_grads(problem, state.x, state.y, batch)
    a2 = hp.alpha2 * hp.eta
    v_new = jax.tree.map(lambda v, d: (1 - a2) * v + a2 * d, state.v, dg)
    zg_new = track_update(state.zg, v_new, state.v, mix)
    y_new = param_update(state.y, zg_new, hp.eta, hp.beta2, mix)
    return mdbo.MDBOState(x=state.x, y=y_new, u=state.u, v=v_new,
                          zf=state.zf, zg=zg_new)


ALGORITHMS: dict[str, Algorithm] = {
    "mdbo": Algorithm(mdbo.init, mdbo.step),
    "vrdbo": Algorithm(vrdbo.init, vrdbo.step),
    "dsbo": Algorithm(_dsbo_init, baselines.dsbo_step),
    "gdsbo": Algorithm(baselines.gdsbo_init, baselines.gdsbo_step),
    "gt_sgd": Algorithm(_gt_sgd_init, _gt_sgd_step),
}


# ---------------------------------------------------------------------------
# Mix-backend registry
# ---------------------------------------------------------------------------

MIX_BACKENDS: dict[str, Callable[..., MixFn]] = {}


def register_mix_backend(name: str):
    def deco(builder):
        MIX_BACKENDS[name] = builder
        return builder
    return deco


@register_mix_backend("dense")
def _dense_backend(*, weights=None, K: int | None = None,
                   self_weight: float = 1.0 / 3.0, axis_name: str = "data"):
    """Paper-faithful einsum with an explicit W (default: ring(K))."""
    if weights is None:
        if K is None:
            raise ValueError("dense mix needs `weights` or `K`")
        weights = ring(K, self_weight).weights
    return dense_mix(weights)


@register_mix_backend("ring_rolled")
def _ring_rolled_backend(*, weights=None, K: int | None = None,
                         self_weight: float = 1.0 / 3.0,
                         axis_name: str = "data"):
    """W-free ring via jnp.roll on the leading node axis."""
    return ring_mix_rolled(self_weight)


@register_mix_backend("ring_local")
def _ring_local_backend(*, weights=None, K: int | None = None,
                        self_weight: float = 1.0 / 3.0,
                        axis_name: str = "data", error_feedback: bool = False,
                        ratio: float = 1.0):
    """Per-shard ring via collective_permute; requires shard_map execution.
    ``error_feedback=True`` (+ ``ratio``) runs EF21-compressed gossip with the
    accumulators living shard-local (``ring_wmi_local`` — no K×K contraction
    ever crosses a shard)."""
    if error_feedback:
        from repro.core.compression import (ErrorFeedbackMix, ring_wmi_local,
                                            topk_sparsify)
        return ErrorFeedbackMix(None, topk_sparsify(ratio),
                                wmi=ring_wmi_local(axis_name, self_weight,
                                                   size=K))
    return ring_mix_local(axis_name, self_weight, size=K)


def _compression_weights(weights, K, self_weight):
    if weights is not None:
        return weights
    if K is None:
        raise ValueError("compressed mix needs `weights` or `K`")
    return ring(K, self_weight).weights


@register_mix_backend("compressed_topk")
def _compressed_topk_backend(*, weights=None, K: int | None = None,
                             self_weight: float = 1.0 / 3.0,
                             axis_name: str = "data", ratio: float = 0.25,
                             error_feedback: bool = False):
    """Compressed gossip A + (W−I)·topk(A): only the top ``ratio`` fraction
    of entries (by magnitude, per node/leaf) crosses the network.
    ``error_feedback=True`` wraps the compressor in EF21 accumulators."""
    from repro.core.compression import (ErrorFeedbackMix, compressed_mix,
                                        topk_sparsify)
    W = _compression_weights(weights, K, self_weight)
    comp = topk_sparsify(ratio)
    return (ErrorFeedbackMix(W, comp) if error_feedback
            else compressed_mix(W, comp))


@register_mix_backend("compressed_rand")
def _compressed_rand_backend(*, weights=None, K: int | None = None,
                             self_weight: float = 1.0 / 3.0,
                             axis_name: str = "data", ratio: float = 0.25,
                             seed: int = 0, error_feedback: bool = False):
    """Compressed gossip with the random sparsifier (keys are a stable
    digest of the leaf path — reproducible across processes). The plain
    form uses the unbiased 1/ratio rescale; the EF21 form needs the
    contractive mask-only variant (the rescale would make the accumulator
    amplify the innovation by 1/ratio per call and diverge — EF supplies
    the bias correction itself)."""
    from repro.core.compression import (ErrorFeedbackMix, compressed_mix,
                                        random_sparsify)
    W = _compression_weights(weights, K, self_weight)
    comp = random_sparsify(ratio, seed=seed, rescale=not error_feedback)
    return (ErrorFeedbackMix(W, comp) if error_feedback
            else compressed_mix(W, comp))


@register_mix_backend("async_gossip")
def _async_gossip_backend(*, weights=None, K: int | None = None,
                          self_weight: float = 1.0 / 3.0,
                          axis_name: str = "data", tau: int = 0,
                          drop_prob=0.0, seed: int = 0,
                          error_feedback: bool = False, ratio: float = 1.0,
                          local: bool = False):
    """Asynchronous stale-by-τ ring gossip (double-buffered neighbor caches
    in the scan carry; per-edge Bernoulli drop model). ``tau=0`` reproduces
    synchronous ring gossip bitwise. ``error_feedback=True`` (+ ``ratio``)
    EF21-compresses the delivered payloads against the caches. ``local=True``
    exchanges via ppermute under shard_map (the Engine sets it automatically
    when built with a mesh). Ring-only: a non-ring ``weights`` (e.g. from an
    erdos/star Topology) is rejected rather than silently remixed on a ring."""
    import numpy as np

    from repro.core.async_gossip import AsyncGossipMix
    from repro.core.compression import topk_sparsify
    from repro.core.topology import ring as ring_topo
    if K is None:
        raise ValueError("async_gossip needs `K` (or a Topology)")
    if weights is not None and not np.allclose(
            np.asarray(weights), ring_topo(K, self_weight).weights):
        raise ValueError(
            "async_gossip only implements the ring topology; got a non-ring "
            f"mixing matrix for K={K} (self_weight={self_weight})")
    comp = topk_sparsify(ratio) if error_feedback else None
    return AsyncGossipMix(K, self_weight=self_weight, tau=tau,
                          drop_prob=drop_prob, seed=seed, compressor=comp,
                          axis_name=axis_name, local=local)


def make_mix(name: str, **kwargs) -> MixFn:
    """Build a mixing operator from the backend registry.

    kwargs: weights (dense / compressed_*), K (default-ring fallback),
    self_weight, axis_name (ring_local / async_gossip), ratio / seed /
    error_feedback (compressed_* / async_gossip), tau / drop_prob / local
    (async_gossip).
    """
    try:
        builder = MIX_BACKENDS[name]
    except KeyError:
        raise ValueError(
            f"unknown mix backend {name!r}; have {sorted(MIX_BACKENDS)}")
    return builder(**kwargs)


# ---------------------------------------------------------------------------
# PRNG key schedule
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnums=1)
def key_schedule(key: jax.Array, steps: int):
    """Per-iteration (batch, node/J̃) subkey pairs — two independent streams.

    Returns (kbs, kns), each of shape (steps, *key). kbs[t] seeds the step-t
    minibatch draw; kns[t] fans out into the K per-node J̃ keys. No key is
    ever used for both purposes (regression-tested in tests/test_engine.py).
    """
    def body(k, _):
        k, kb, kn = jax.random.split(k, 3)
        return k, (kb, kn)

    _, (kbs, kns) = jax.lax.scan(body, key, None, length=steps)
    return kbs, kns


# ---------------------------------------------------------------------------
# Results container
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class RunResult:
    algo: str
    steps: list[int]
    upper_loss: list[float]
    lower_loss: list[float]
    consensus_x: list[float]
    consensus_y: list[float]
    extra: dict[str, list[float]]
    wall_time_s: float = 0.0

    def as_rows(self):
        for i, t in enumerate(self.steps):
            yield {"algo": self.algo, "step": t,
                   "upper_loss": self.upper_loss[i],
                   "lower_loss": self.lower_loss[i],
                   "consensus_x": self.consensus_x[i],
                   "consensus_y": self.consensus_y[i],
                   **{k: v[i] for k, v in self.extra.items()}}


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------

class Engine:
    """Unified run substrate: algorithm × mix backend × dispatch × mesh.

    Parameters
    ----------
    topo: a :class:`Topology` (its W feeds the dense backend) or a bare node
        count K.
    algo: one of :data:`ALGORITHMS`.
    mix: one of :data:`MIX_BACKENDS`. ``ring_local`` additionally needs
        ``mesh`` (one node per shard of ``axis_name``).
    dispatch: ``fused`` (lax.scan chunks of ``eval_every`` steps, donated
        state) or ``per_step`` (legacy one-jit-call-per-step loop).
    mesh / axis_name: mesh execution. ``axis_name`` is the node axis of the
        mesh — ``data`` for per-node parameter copies (dp), ``pod`` for
        FSDP-inside-a-node pods (fsdp_gt). ``ring_local`` shard_maps the
        algorithm body over that axis; other backends run under GSPMD with
        the state placed node-sharded.
    """

    def __init__(self, problem: BilevelProblem, cfg: HypergradConfig,
                 hp: HParams, topo: Topology | int, *, algo: str = "mdbo",
                 mix: str = "dense", dispatch: str = "fused",
                 self_weight: float = 1.0 / 3.0, axis_name: str = "data",
                 mesh=None, donate: bool = True,
                 mix_kwargs: dict | None = None, recorder=None):
        if isinstance(topo, Topology):
            self.K, weights = topo.size, topo.weights
        else:
            self.K, weights = int(topo), None
        if algo not in ALGORITHMS:
            raise ValueError(f"unknown algo {algo!r}; have {sorted(ALGORITHMS)}")
        if dispatch not in ("fused", "per_step"):
            raise ValueError(f"dispatch must be fused|per_step, got {dispatch!r}")
        self.problem, self.cfg, self.hp = problem, cfg, hp
        self.algo, self.mix_name, self.dispatch = algo, mix, dispatch
        self.axis_name, self.mesh = axis_name, mesh
        mk = dict(mix_kwargs or {})
        if mix == "async_gossip" and mesh is not None:
            mk.setdefault("local", True)  # ppermute exchange, one node/shard
        self.mix = make_mix(mix, weights=weights, K=self.K,
                            self_weight=self_weight, axis_name=axis_name,
                            **mk)
        if recorder is None:
            from repro.obs.recorder import NullRecorder
            recorder = NullRecorder()
        self.recorder = recorder
        # static inputs for the obs bytes-per-mix-round estimate
        self._weights = weights
        self._mix_ratio = float(mk.get("ratio", 1.0))
        self._mix_stateful = bool(getattr(self.mix, "stateful", False))
        # shard-local backends run the algorithm body under shard_map; their
        # carry state (EF accumulators, async neighbor caches) all carries a
        # leading node axis, so the P(axis_name) prefix shards it too.
        self._shard_local = (mix == "ring_local"
                             or bool(getattr(self.mix, "shard_local", False)))
        if self._shard_local and mesh is None:
            raise ValueError(f"mix={mix!r} runs under shard_map and needs a "
                             f"mesh with axis `axis_name` of size K")
        alg = ALGORITHMS[algo]
        self._init_body = partial(alg.init, problem, cfg, hp, self.mix)
        self._step_nomix = partial(alg.step, problem, cfg, hp)
        self._step_body = partial(alg.step, problem, cfg, hp, self.mix)
        # node-axis sharding for mesh runs (GSPMD path; ring_local re-shards
        # through its shard_map in_specs anyway)
        self._node_sharding = (NamedSharding(mesh, P(axis_name))
                               if mesh is not None else None)
        # buffer donation is a no-op (and warns) on CPU
        self._donate = (0,) if donate and jax.default_backend() != "cpu" else ()
        self._jit_cache: dict = {}

    # -- carry plumbing (stateful mixes thread EF accumulators) -------------

    def _carry_step(self, carry, batch, nkeys):
        """One algorithm step over the scan carry. For stateful mixes the
        carry is (state, mix_states); the per-call-site accumulators are
        rebound each step in trace order."""
        if not self._mix_stateful:
            return self._step_body(carry, batch, nkeys)
        state, mstates = carry
        mix, out = self.mix.bind(mstates)
        new_state = self._step_nomix(mix, state, batch, nkeys)
        return (new_state, tuple(out))

    def _carry_state(self, carry):
        return carry[0] if self._mix_stateful else carry

    def _mix_sites(self, state, batch, nkeys) -> list:
        """Per-call-site abstract shape trees of one step's mix invocations,
        discovered with eval_shape — trace order is deterministic. Shared by
        the stateful-mix carry seeding and the obs bytes-per-round metric."""
        sites: list = []

        def probe(tree):
            sites.append(jax.tree.map(
                lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), tree))
            return tree

        jax.eval_shape(lambda s, b, k: self._step_nomix(probe, s, b, k),
                       state, batch, nkeys)
        return sites

    def _mix_state0(self, state, batch, nkeys):
        """Initial mix-carry slots, one per mix call site of a step. The
        mix's ``state0(site_shapes, site_index)`` builds each slot (EF: a
        zero accumulator; async gossip: zero caches + ages + drop keys);
        mixes without one get zeros shaped like the mixed tree."""
        sites = self._mix_sites(state, batch, nkeys)
        make0 = getattr(self.mix, "state0", None)
        if make0 is not None:
            return tuple(make0(t, i) for i, t in enumerate(sites))
        return tuple(jax.tree.map(lambda sd: jnp.zeros(sd.shape, sd.dtype), t)
                     for t in sites)

    def _obs_mset(self, state, batch, nkeys):
        """Memoized trainer MetricSet for in-scan accumulation (consensus,
        update/estimator norms, mix bytes, async staleness histogram)."""
        if "mset" not in self._jit_cache:
            from repro.obs.metrics import trainer_metric_set
            sites = self._mix_sites(state, batch, nkeys)
            self._jit_cache["mset"] = trainer_metric_set(
                state, mix=self.mix, mix_sites=sites, ratio=self._mix_ratio,
                weights=self._weights)
        return self._jit_cache["mset"]

    # -- building blocks ----------------------------------------------------

    def _sharded(self, fn, n_in: int):
        """Wrap an algorithm body in shard_map for shard-local backends
        (ring_local, async_gossip-with-mesh). The single spec is a tree
        prefix, so it also shards stateful-mix carry tuples — every carry
        leaf has a leading node axis."""
        if not self._shard_local:
            return fn
        spec = P(self.axis_name)
        return jax.shard_map(fn, mesh=self.mesh, in_specs=(spec,) * n_in,
                             out_specs=spec, check_vma=False)

    def _cached(self, name: str, build: Callable):
        if name not in self._jit_cache:
            self._jit_cache[name] = build()
        return self._jit_cache[name]

    @property
    def init(self):
        """jit-ed init(X0, Y0, batch, keys) -> state. Stateful mixes run
        their stateless (zero-accumulator) form at t=0."""
        return self._cached("init", lambda: jax.jit(
            self._sharded(self._init_body, 4)))

    @property
    def step(self):
        """jit-ed step(carry, batch, node_keys) -> carry (per-step dispatch).
        The carry is the algorithm state, or (state, mix_states) for
        stateful mixes."""
        return self._cached("step", lambda: jax.jit(
            self._sharded(self._carry_step, 3)))

    @property
    def evaluate(self):
        """jit-ed evaluate(state, eval_batch) -> {upper, lower, cx, cy}."""
        def build():
            def ev(state, eval_batch):
                xbar, ybar = node_mean(state.x), node_mean(state.y)
                return {
                    "upper": self.problem.upper_loss(xbar, ybar, eval_batch),
                    "lower": self.problem.lower_loss(xbar, ybar, eval_batch),
                    "cx": consensus_error(state.x),
                    "cy": consensus_error(state.y),
                }
            return jax.jit(ev)
        return self._cached("evaluate", build)

    def _make_chunk(self, sample_batch, host: bool, mset=None):
        """Scan-fused multi-step kernel. Three flavors:

        * ring_local: shard_map(scan) over pre-stacked batches + node keys;
        * host sampler: scan over pre-stacked batches, in-scan diagnostics;
        * device sampler: sampling *inside* the scan — the whole eval
          interval is one device program with no host round-trips.

        With ``mset`` (obs enabled, non-shard-local) the chunk additionally
        threads the metric accumulator through the scan carry —
        ``chunk(carry, macc, ...) -> (carry, macc, trace)`` — so metric
        accumulation rides the same device program and the algorithm's own
        operation stream is untouched (the fused==per-step bitwise contract
        holds with obs on; pinned in tests/test_obs.py).
        """
        K = self.K

        if self._shard_local:
            def chunk(carry, batches, nkeys):
                def body(c, x):
                    b, nk = x
                    return self._carry_step(c, b, nk), None
                return jax.lax.scan(body, carry, (batches, nkeys))[0]

            spec, tspec = P(self.axis_name), P(None, self.axis_name)
            chunk = jax.shard_map(chunk, mesh=self.mesh,
                                  in_specs=(spec, tspec, tspec),
                                  out_specs=spec, check_vma=False)
            return jax.jit(chunk, donate_argnums=self._donate)

        def obs_body(cm, batch, nkeys):
            c, m = cm
            old = self._carry_state(c)
            c = self._carry_step(c, batch, nkeys)
            s = self._carry_state(c)
            m = mset.update(m, {
                "old": old, "new": s,
                "mix_states": c[1] if self._mix_stateful else None})
            return (c, m), (consensus_error(s.x), consensus_error(s.y))

        if host:
            if mset is not None:
                def chunk(carry, macc, batches, nkeys):
                    def body(cm, x):
                        b, nk = x
                        return obs_body(cm, b, nk)
                    (c, m), trace = jax.lax.scan(body, (carry, macc),
                                                 (batches, nkeys))
                    return c, m, trace
            else:
                def chunk(carry, batches, nkeys):
                    def body(c, x):
                        b, nk = x
                        c = self._carry_step(c, b, nk)
                        s = self._carry_state(c)
                        return c, (consensus_error(s.x), consensus_error(s.y))
                    return jax.lax.scan(body, carry, (batches, nkeys))
        else:
            if mset is not None:
                def chunk(carry, macc, kbs, kns):
                    def body(cm, kk):
                        kb, kn = kk
                        return obs_body(cm, sample_batch(kb),
                                        jax.random.split(kn, K))
                    (c, m), trace = jax.lax.scan(body, (carry, macc),
                                                 (kbs, kns))
                    return c, m, trace
            else:
                def chunk(carry, kbs, kns):
                    def body(c, kk):
                        kb, kn = kk
                        c = self._carry_step(c, sample_batch(kb),
                                             jax.random.split(kn, K))
                        s = self._carry_state(c)
                        return c, (consensus_error(s.x), consensus_error(s.y))
                    return jax.lax.scan(body, carry, (kbs, kns))

        return jax.jit(chunk, donate_argnums=self._donate)

    def _chunk_fn(self, sample_batch, host: bool, mset=None):
        # keyed on the sampler OBJECT: the cache entry pins a strong
        # reference so a recycled id() can never resurrect a chunk that
        # closes over a dead sampler. The obs flag forks the cache: the obs
        # chunk has a different signature (it threads the metric accumulator).
        key = ("chunk", id(sample_batch), host, mset is not None)
        hit = self._jit_cache.get(key)
        if hit is None or hit[0] is not sample_batch:
            self._jit_cache[key] = (sample_batch,
                                    self._make_chunk(sample_batch, host,
                                                     mset))
        return self._jit_cache[key][1]

    def _stack_batches(self, sample_batch, kb_chunk, host: bool):
        """Per-step batches stacked on a leading time axis for the scan.
        Mesh runs place the stack node-sharded (time axis replicated)."""
        if host:
            bs = [sample_batch(kb_chunk[i]) for i in range(kb_chunk.shape[0])]
            out = jax.tree.map(lambda *xs: jnp.stack(xs), *bs)
        else:
            out = jax.vmap(sample_batch)(kb_chunk)
        if self.mesh is not None:
            tsh = NamedSharding(self.mesh, P(None, self.axis_name))
            out = jax.tree.map(lambda a: jax.device_put(a, tsh), out)
        return out

    # -- the run loop -------------------------------------------------------

    def run(self, sample_batch: Callable[[jax.Array], Any] | Sampler,
            eval_batch: Any, steps: int, seed: int = 0, eval_every: int = 10,
            init_batch_scale: int = 1,
            extra_metrics: Callable[[Any, Any], dict] | None = None,
            x0: Any | None = None, y0: Any | None = None,
            return_state: bool = False,
            on_eval: Callable[[int, Any], None] | None = None) -> RunResult:
        """Run the configured algorithm for ``steps`` iterations.

        sample_batch is a :class:`Sampler` or bare callable returning
        {'f','g','h'} with node axis K (and J axis on 'h'); eval_batch is a
        *global* batch for diagnostics. ``on_eval(t, state)`` fires after
        every recorded eval boundary (t=0 included) — the checkpointing hook
        used by ``repro.launch.train``.
        """
        del init_batch_scale  # accepted for API compatibility
        K = self.K
        host = is_host_sampler(sample_batch)

        key = jax.random.PRNGKey(seed)
        kx, ky, key = jax.random.split(key, 3)
        X0 = replicate(self.problem.init_x(kx) if x0 is None else x0, K,
                       sharding=self._node_sharding)
        Y0 = replicate(self.problem.init_y(ky) if y0 is None else y0, K,
                       sharding=self._node_sharding)

        key, k0 = jax.random.split(key)
        kb0, kn0 = jax.random.split(k0)  # independent batch / J̃ init keys
        b0, nk0 = sample_batch(kb0), jax.random.split(kn0, K)
        state = self.init(X0, Y0, b0, nk0)
        del X0, Y0   # a model-sized copy per node: free it for the run
        carry = ((state, self._mix_state0(state, b0, nk0))
                 if self._mix_stateful else state)
        kbs, kns = key_schedule(key, steps)

        in_scan = self.dispatch == "fused" and not self._shard_local
        rec = self.recorder
        # In-scan metric accumulation rides the fused chunk only; per_step
        # and shard_local dispatch record eval-boundary gauges alone (metric
        # reduction out of shard_map is out of scope — documented in
        # docs/observability.md).
        obs_in_scan = in_scan and rec.enabled
        mset = self._obs_mset(state, b0, nk0) if obs_in_scan else None
        obs_in_scan = obs_in_scan and len(mset) > 0
        res = RunResult(self.algo, [], [], [], [], [], {})
        t0 = time.perf_counter()

        def record(t, state, trace=None):
            with rec.span("eval", step=t):
                m = self.evaluate(state, eval_batch)
                res.steps.append(t)
                res.upper_loss.append(float(m["upper"]))
                res.lower_loss.append(float(m["lower"]))
                res.consensus_x.append(float(m["cx"]))
                res.consensus_y.append(float(m["cy"]))
                if in_scan:
                    # in-scan accumulated diagnostics: chunk-mean consensus
                    cx, cy = ((float(jnp.mean(trace[0])),
                               float(jnp.mean(trace[1])))
                              if trace is not None
                              else (float(m["cx"]), float(m["cy"])))
                    res.extra.setdefault("scan_cx_mean", []).append(cx)
                    res.extra.setdefault("scan_cy_mean", []).append(cy)
                extras = (extra_metrics(state, eval_batch)
                          if extra_metrics is not None else {})
                for k, v in extras.items():
                    res.extra.setdefault(k, []).append(float(v))
                if rec.enabled:
                    rec.metrics({"eval_upper_loss": res.upper_loss[-1],
                                 "eval_lower_loss": res.lower_loss[-1],
                                 "eval_consensus_x": res.consensus_x[-1],
                                 "eval_consensus_y": res.consensus_y[-1],
                                 **{f"eval_{k}": float(v)
                                    for k, v in extras.items()}}, step=t)
                if on_eval is not None:
                    on_eval(t, state)

        record(0, self._carry_state(carry))

        if self.dispatch == "per_step":
            for t in range(1, steps + 1):
                carry = self.step(carry, sample_batch(kbs[t - 1]),
                                  jax.random.split(kns[t - 1], K))
                if t % eval_every == 0 or t == steps:
                    rec.counter_add("train_steps", eval_every
                                    if t % eval_every == 0 else t % eval_every)
                    record(t, self._carry_state(carry))
        else:
            chunk = self._chunk_fn(sample_batch, host,
                                   mset if obs_in_scan else None)
            macc = mset.init() if obs_in_scan else None
            t = 0
            while t < steps:
                n = min(eval_every, steps - t)
                kb_c, kn_c = kbs[t:t + n], kns[t:t + n]
                with rec.span("train_chunk", t0=t, steps=n):
                    if self._shard_local:
                        xs = self._stack_batches(sample_batch, kb_c, host)
                        nk = jax.vmap(lambda k: jax.random.split(k, K))(kn_c)
                        carry, trace = chunk(carry, xs, nk), None
                    elif host:
                        xs = self._stack_batches(sample_batch, kb_c, host)
                        nk = jax.vmap(lambda k: jax.random.split(k, K))(kn_c)
                        if obs_in_scan:
                            carry, macc, trace = chunk(carry, macc, xs, nk)
                        else:
                            carry, trace = chunk(carry, xs, nk)
                    elif obs_in_scan:
                        carry, macc, trace = chunk(carry, macc, kb_c, kn_c)
                    else:
                        carry, trace = chunk(carry, kb_c, kn_c)
                t += n
                rec.counter_add("train_steps", n)
                if obs_in_scan:
                    # drain at the chunk boundary (the host is already
                    # syncing for the eval record below) and reset the
                    # accumulator for the next chunk
                    rec.record_drain(mset.drain(macc), step=t)
                    macc = mset.init()
                record(t, self._carry_state(carry), trace)

        res.wall_time_s = time.perf_counter() - t0
        if rec.enabled:
            rec.event("run_done", algo=self.algo, steps=steps,
                      wall_time_s=res.wall_time_s)
            rec.flush()
        return (res, self._carry_state(carry)) if return_state else res
