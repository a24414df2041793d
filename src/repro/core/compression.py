"""Communication compression for the gossip step (beyond-paper extension).

The paper's related work (Koloskova et al. 2019; Tang et al. 2019) improves
decentralized *single-level* methods by compressing communicated variables.
This module lifts the idea to the bilevel algorithms: the mixing step becomes

    X_{t+1} ← X_t + (W − I) C(X_t)        (compressed-gossip form)

where ``C`` is a per-leaf sparsifier. Only the compressed values would cross
the network, so communicated bytes drop by the keep-ratio while the self term
stays exact. Used by benchmarks/fig_compression.py to chart the
bytes-vs-convergence tradeoff; not enabled in the paper-faithful baselines.

Module contract: every function here is **pure JAX** and acts on node-stacked
trees (leading axis K). The only state — the EF21 accumulators of
:class:`ErrorFeedbackMix` — lives in the engine's *scan carry* (threaded per
call site via :meth:`ErrorFeedbackMix.bind` / :meth:`ErrorFeedbackMix.state0`),
never on the host; :func:`ef21_update` is the shared innovation-update rule
also used by :class:`repro.core.async_gossip.AsyncGossipMix` to compose
compression with stale gossip. The ``(W − I)·h`` application is pluggable:
dense by default, or a shard-local ring operator (``ring_wmi_rolled`` /
``ring_wmi_local``) so the accumulators can live one-node-per-shard under the
engine's ``ring_local`` shard_map backend.
"""
from __future__ import annotations

import hashlib
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.hypergrad import tree_add, tree_sub
from repro.core.tracking import MixFn


def _path_seed(path) -> int:
    """Stable 31-bit digest of a pytree key path.

    Python's ``hash(str(path))`` is salted per process (PYTHONHASHSEED), so
    keys derived from it made compressed runs irreproducible across
    processes; blake2s is deterministic everywhere."""
    digest = hashlib.blake2s(jax.tree_util.keystr(path).encode()).digest()
    return int.from_bytes(digest[:4], "little") % (2 ** 31)


def topk_sparsify(ratio: float) -> Callable:
    """Keep the top ``ratio`` fraction of entries by magnitude, per node and
    per leaf (deterministic; the classic top-k compressor)."""
    assert 0.0 < ratio <= 1.0

    def compress(tree):
        def leaf(a):
            if ratio >= 1.0:
                return a
            flat = a.reshape(a.shape[0], -1)           # [K, d]
            d = flat.shape[1]
            k = max(int(d * ratio), 1)
            # threshold = k-th largest magnitude per node
            thresh = jax.lax.top_k(jnp.abs(flat), k)[0][:, -1:]
            mask = jnp.abs(flat) >= thresh
            return (flat * mask).reshape(a.shape).astype(a.dtype)
        return jax.tree.map(leaf, tree)

    return compress


def random_sparsify(ratio: float, seed: int = 0,
                    rescale: bool = True) -> Callable:
    """Keep a random ``ratio`` fraction (unbiased up to 1/ratio scaling).

    ``rescale=False`` drops the 1/ratio factor, giving the *contractive*
    (biased) variant: ‖v − C(v)‖ ≤ ‖v‖. Error feedback requires it — with
    the unbiased rescale the EF21 accumulator update h' = h + C(v − h)
    overshoots kept coordinates by 1/ratio and diverges geometrically."""
    assert 0.0 < ratio <= 1.0

    def compress(tree):
        def leaf(path, a):
            if ratio >= 1.0:
                return a
            key = jax.random.fold_in(jax.random.PRNGKey(seed), _path_seed(path))
            mask = jax.random.bernoulli(key, ratio, a.shape)
            kept = a * mask
            return (kept / ratio if rescale else kept).astype(a.dtype)
        return jax.tree_util.tree_map_with_path(leaf, tree)

    return compress


def compressed_mix(W, compressor: Callable) -> MixFn:
    """Gossip with compressed neighbor contributions:
    mix(A) = A + (W − I) C(A).  Exact when C = identity."""
    import numpy as np
    Wm = jnp.asarray(np.asarray(W) - np.eye(np.asarray(W).shape[0]))

    def mix(tree):
        comp = compressor(tree)

        def leaf(a, c):
            return (a + jnp.tensordot(Wm, c, axes=([1], [0]))).astype(a.dtype)

        return jax.tree.map(leaf, tree, comp)

    return mix


def ef21_update(h, fresh, compressor: Callable):
    """The EF21 innovation rule: ``h' = h + C(fresh − h)``.

    ``h`` is the receiver's proxy of the sender's value; only ``C(fresh − h)``
    crosses the network. Shared by :class:`ErrorFeedbackMix` and the
    stale-gossip composition in :class:`repro.core.async_gossip.AsyncGossipMix`.
    """
    return tree_add(h, compressor(tree_sub(fresh, h)))


def dense_wmi(W) -> Callable:
    """``tree ↦ (W − I)·tree`` via einsum with the full K×K matrix."""
    Wn = np.asarray(W)
    Wm = jnp.asarray(Wn - np.eye(Wn.shape[0]))

    def apply(tree):
        return jax.tree.map(
            lambda hh: jnp.tensordot(Wm, hh, axes=([1], [0])), tree)

    return apply


def ring_wmi_rolled(self_weight: float = 1.0 / 3.0) -> Callable:
    """``(W − I)·tree`` for the ring, W-free via jnp.roll (single-process)."""
    nb = (1.0 - self_weight) / 2.0

    def apply(tree):
        return jax.tree.map(
            lambda h: (nb * jnp.roll(h, 1, axis=0) + nb * jnp.roll(h, -1, axis=0)
                       - (1.0 - self_weight) * h), tree)

    return apply


def ring_wmi_local(axis_name: str, self_weight: float = 1.0 / 3.0,
                   size: int | None = None) -> Callable:
    """``(W − I)·tree`` for the ring inside shard_map: two ppermutes, the
    accumulator slice stays shard-local (one node per shard of ``axis_name``)."""
    nb = (1.0 - self_weight) / 2.0

    def apply(tree):
        n = jax.lax.axis_size(axis_name) if size is None else size
        to_left = [(i, (i - 1) % n) for i in range(n)]
        to_right = [(i, (i + 1) % n) for i in range(n)]

        def leaf(h):
            from_right = jax.lax.ppermute(h, axis_name, to_left)
            from_left = jax.lax.ppermute(h, axis_name, to_right)
            return nb * from_left + nb * from_right - (1.0 - self_weight) * h

        return jax.tree.map(leaf, tree)

    return apply


class ErrorFeedbackMix:
    """EF21-style stateful compressed gossip (Richtárik et al., 2021).

    Plain ``compressed_mix`` communicates C(A) directly, so the gossip fixed
    point is biased by the compression error. Error feedback keeps, per gossip
    call site, a device-resident proxy ``h`` of what the neighbors have
    reconstructed so far and only compresses the *innovation*:

        c_t = C(A_t − h_{t−1});   h_t = h_{t−1} + c_t
        mix(A_t) = A_t + (W − I) h_t

    Only ``c_t`` would cross the network. As the iterates converge, the
    innovation shrinks, ``h → A`` and the mix approaches the exact ``W·A`` —
    aggressive ratios stop biasing the fixed point.

    The ``(W − I)·h`` product defaults to the dense einsum with ``W``; pass
    ``wmi`` (e.g. :func:`ring_wmi_local`) to run it shard-local under the
    engine's ``ring_local`` shard_map backend, where a K×K contraction cannot
    act across shards. The engine threads the per-call-site accumulators
    through its scan carry via :meth:`bind` / :meth:`state0`; a direct
    ``__call__`` is the stateless ``h ≡ 0`` special case (identical to plain
    ``compressed_mix``), used for the t=0 init.
    """

    stateful = True

    def __init__(self, W, compressor: Callable, wmi: Callable | None = None):
        if W is None and wmi is None:
            raise ValueError("ErrorFeedbackMix needs W or an explicit wmi")
        self.wmi = dense_wmi(W) if wmi is None else wmi
        self.compressor = compressor

    def state0(self, site_shapes, site_index: int):
        """t=0 carry slot: a zero accumulator shaped like the mixed tree."""
        del site_index
        return jax.tree.map(lambda sd: jnp.zeros(sd.shape, sd.dtype),
                            site_shapes)

    def apply(self, tree, h):
        """One EF21 update: (mixed tree, updated accumulator)."""
        h_new = ef21_update(h, tree, self.compressor)
        wh = self.wmi(h_new)
        mixed = jax.tree.map(lambda a, d: (a + d).astype(a.dtype), tree, wh)
        return mixed, h_new

    def __call__(self, tree):
        h0 = jax.tree.map(jnp.zeros_like, tree)
        return self.apply(tree, h0)[0]

    def bind(self, states):
        """Close over per-call-site accumulators for one traced step.

        ``states`` is a sequence of ``h`` trees consumed in trace order (the
        call order inside an algorithm step is deterministic, so site *i*
        always corresponds to the same mixed variable). Returns ``(mix, out)``
        where ``out`` collects the updated accumulators in the same order.
        """
        it = iter(states)
        out: list = []

        def mix(tree):
            mixed, h_new = self.apply(tree, next(it))
            out.append(h_new)
            return mixed

        return mix, out


def neighbor_degree(W) -> int:
    """Max number of neighbors a node sends to under mixing matrix W: the
    count of nonzero off-diagonal entries in its densest row."""
    Wn = np.asarray(W)
    off = (np.abs(Wn) > 0) & ~np.eye(Wn.shape[0], dtype=bool)
    return int(off.sum(axis=1).max())


def comm_bytes_per_mix(tree, ratio: float, W=None) -> int:
    """Communicated payload per gossip round per node:
    degree · ratio · (values + indices).

    The neighbor degree comes from the mixing matrix ``W`` (nonzero
    off-diagonal entries per row); W=None assumes the 2-neighbor ring the
    paper benchmarks on."""
    degree = 2 if W is None else neighbor_degree(W)
    total = 0
    for a in jax.tree.leaves(tree):
        d = a.size // a.shape[0]
        kept = max(int(d * ratio), 1)
        per_entry = a.dtype.itemsize + (4 if ratio < 1.0 else 0)  # + index
        total += degree * kept * per_entry
    return total
