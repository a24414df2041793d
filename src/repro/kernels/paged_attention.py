"""Paged decode attention (TPU Pallas): one new query token per slot attends
over that slot's KV pages *through its block table* — the physical pool is
never materialized into a per-slot dense logical cache.

TPU-native design notes (vs the dense ``_flash_kernel``):
  * Grid is (B, n_pages) with the page dimension innermost — the
    online-softmax running state (m, l, acc) lives in VMEM scratch persisting
    across a slot's pages, exactly like the k-block dimension of the flash
    kernel.
  * The block table and per-slot lengths are **scalar-prefetch** operands
    (``pltpu.PrefetchScalarGridSpec``): the k/v BlockSpec index_map reads
    ``tables[b, j]`` to aim each page DMA at a physical block, so only the
    pages a slot actually owns are ever pulled from HBM.
  * One page DMA carries every KV head: the K/V block is
    ``(1, block_size, 1, Hkv, Dh)``, whose two minor dims are the full pool
    dims — the TPU tiling rule (minor dims divisible by (8, 128) or equal to
    the array's) refuses a one-head block once Hkv is not a multiple of 8
    (smollm-360m: Hkv=5), and Mosaic cannot prove a grid-indexed head offset
    aligned. The kernel walks the heads with static indices instead.
  * Pages past a slot's used length are clamped to the *last valid* page in
    the index_map — consecutive grid steps with an unchanged block index skip
    the DMA (TPU revolving-buffer rule), so dead/out-of-range pages cost
    neither bandwidth nor compute (their math is ``pl.when``-pruned).
  * Tail-block masking: the last page is partially filled; a positional
    ``pos < length`` mask zeroes the unwritten lanes, which is what keeps
    trash-block garbage (dead slots, unallocated table entries) out of every
    result.
  * GQA is native: each KV head's ``G = H // Hkv`` grouped query heads are
    computed against the loaded page, so grouped configs serve without
    replicating K/V.

The pool layout matches ``repro.serve.batch.BlockPool`` for attention
families: ``[num_blocks + 1, block_size, L, Hkv, Dh]`` with the trailing
trash block at index ``num_blocks``; ``layer`` selects the transformer layer
so the serving layer-scan calls the kernel without slicing the pool.

Validated on CPU with interpret=True against
``repro.kernels.ref.paged_attention_ref`` (tests/test_kernels.py); compiled
for a described TPU v5e in tests/test_tpu_compile.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(tables_ref, lengths_ref, layer_ref, q_ref, k_ref, v_ref,
                  o_ref, m_scr, l_scr, acc_scr, *, scale: float,
                  block_size: int, n_pages: int, q_len: int, group: int,
                  n_kv_heads: int):
    """Q query rows per slot and KV head: the flattened [Q*G, ...] row axis
    carries both the window position (row // G) and the grouped query head
    (row % G). Single-token decode is the Q=1 case, whose per-row causal
    mask reduces to the tail-block mask ``pos < length``."""
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = lengths_ref[b]

    # page-level pruning on the LAST row's reach (row Q-1 sees the most):
    # a page past it holds nothing any row may read (dead slots: length 0)
    @pl.when(j * block_size < length)
    def _compute():
        for h in range(n_kv_heads):   # static head index: see module notes
            q = q_ref[0, h].astype(jnp.float32) * scale      # [Q*G, Dh]
            k = k_ref[0, :, 0, h].astype(jnp.float32)        # [bs, Dh]
            v = v_ref[0, :, 0, h].astype(jnp.float32)        # [bs, Dh]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)

            # per-row causal mask: row r (window position r = flat // G)
            # attends positions < length - (Q - 1 - r); it subsumes the
            # tail-block mask
            pos = j * block_size + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // group
            s = jnp.where(pos < length - (q_len - 1 - row), s, NEG_INF)

            m_prev = m_scr[h]                                # [Q*G, 1]
            m_cur = jnp.max(s, axis=-1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            p = jnp.exp(s - m_new)                           # [Q*G, bs]
            alpha = jnp.exp(m_prev - m_new)                  # [Q*G, 1]
            l_scr[h] = alpha * l_scr[h] + jnp.sum(p, axis=-1, keepdims=True)
            acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[h] = m_new

    @pl.when(j == n_pages - 1)
    def _finalize():
        for h in range(n_kv_heads):
            l = l_scr[h]
            l = jnp.where(l == 0.0, 1.0, l)   # fully-masked row: zeros, not NaN
            o_ref[0, h] = (acc_scr[h] / l).astype(o_ref.dtype)


def _grid_spec(B, n_pages, rows, Hkv, Dh, block_size):
    """Grid (slot, page) over q/o blocks of one slot's ``[Hkv, rows, Dh]``
    and K/V blocks of one page's ``[block_size, 1, Hkv, Dh]``."""

    def kv_map(b, j, tables, lengths, layer):
        # out-of-range pages re-target the slot's last valid page (the LAST
        # row's reach bounds every row's): the block index is unchanged from
        # the previous grid step, so the DMA is skipped (compute is pruned by
        # pl.when on the same predicate)
        last = jnp.maximum(lengths[b] - 1, 0) // block_size
        return (tables[b, jnp.minimum(j, last)], 0, layer[0], 0, 0)

    def q_map(b, j, *refs):
        return (b, 0, 0, 0)

    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, n_pages),
        in_specs=[
            pl.BlockSpec((1, Hkv, rows, Dh), q_map),
            pl.BlockSpec((1, block_size, 1, Hkv, Dh), kv_map),
            pl.BlockSpec((1, block_size, 1, Hkv, Dh), kv_map),
        ],
        out_specs=pl.BlockSpec((1, Hkv, rows, Dh), q_map),
        scratch_shapes=[
            pltpu.VMEM((Hkv, rows, 1), jnp.float32),    # running max m
            pltpu.VMEM((Hkv, rows, 1), jnp.float32),    # running denom l
            pltpu.VMEM((Hkv, rows, Dh), jnp.float32),   # fp32 accumulator
        ],
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention_multi(q, k_pages, v_pages, tables, lengths, layer=0, *,
                          interpret: bool = False):
    """Block-table attention for a window of Q candidate tokens per slot —
    the speculative-decoding verify read path (and the stepping stone toward
    paged prefill): one batched dispatch attends all Q rows causally through
    the block table.

    q: [B, Q, H, Dh] — Q new tokens per slot, RoPE already applied, their K/V
      already appended to the pool at positions ``lengths - Q .. lengths-1``.
    k_pages/v_pages: [num_blocks + 1, block_size, L, Hkv, Dh] physical pool.
    tables: [B, n_pages] int32 block tables (clamped or full width).
    lengths: [B] int32 — valid KV count per slot AFTER all Q appends
      (0 = dead slot -> zeros). Row r masks to ``< lengths - (Q - 1 - r)``.
    layer: int32 scalar selecting the transformer layer inside the pool.

    Returns [B, Q, H, Dh] in q.dtype. Identical grid/scratch scheme to
    :func:`paged_attention` with the row axis widened from G to Q*G.
    """
    B, Q, H, Dh = q.shape
    _, block_size, L, Hkv, _ = k_pages.shape
    assert H % Hkv == 0, (H, Hkv)
    G = H // Hkv
    n_pages = tables.shape[1]
    # [B, Q, Hkv, G, Dh] -> [B, Hkv, Q*G, Dh]: rows ordered window-major so
    # the kernel recovers the window position as row // G
    q4 = q.reshape(B, Q, Hkv, G, Dh).transpose(0, 2, 1, 3, 4).reshape(
        B, Hkv, Q * G, Dh)
    kernel = functools.partial(_paged_kernel, scale=Dh ** -0.5,
                               block_size=block_size, n_pages=n_pages,
                               q_len=Q, group=G, n_kv_heads=Hkv)
    out = pl.pallas_call(
        kernel,
        grid_spec=_grid_spec(B, n_pages, Q * G, Hkv, Dh, block_size),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, Q * G, Dh), q.dtype),
        interpret=interpret,
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q4, k_pages, v_pages)
    return out.reshape(B, Hkv, Q, G, Dh).transpose(0, 2, 1, 3, 4).reshape(
        B, Q, H, Dh)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q, k_pages, v_pages, tables, lengths, layer=0, *,
                    interpret: bool = False):
    """Block-table decode attention for one new token per slot.

    q: [B, H, Dh] — the new token's queries (RoPE already applied).
    k_pages/v_pages: [num_blocks + 1, block_size, L, Hkv, Dh] physical pool
      (``BlockPool.data['kv']`` layout; the trailing block is trash).
    tables: [B, n_pages] int32 — each slot's block table (possibly clamped to
      the live high-water page count); unallocated entries point at trash.
    lengths: [B] int32 — valid KV positions per slot (``idx + 1`` after the
      tail append; 0 for dead slots, which then emit zeros).
    layer: int32 scalar selecting the transformer layer inside the pool.

    Returns [B, H, Dh] in q.dtype.
    """
    B, H, Dh = q.shape
    _, block_size, L, Hkv, _ = k_pages.shape
    assert H % Hkv == 0, (H, Hkv)
    G = H // Hkv
    n_pages = tables.shape[1]
    kernel = functools.partial(_paged_kernel, scale=Dh ** -0.5,
                               block_size=block_size, n_pages=n_pages,
                               q_len=1, group=G, n_kv_heads=Hkv)
    out = pl.pallas_call(
        kernel,
        grid_spec=_grid_spec(B, n_pages, G, Hkv, Dh, block_size),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, Dh), q.dtype),
        interpret=interpret,
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q.reshape(B, Hkv, G, Dh),
      k_pages, v_pages)
    return out.reshape(B, H, Dh)
