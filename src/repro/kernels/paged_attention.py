"""Paged decode attention (TPU Pallas): one new query token per slot attends
over that slot's KV pages *through its block table* — the physical pool is
never materialized into a per-slot dense logical cache.

TPU-native design notes (vs the dense ``_flash_kernel``):
  * One grid step walks a group of P = min(8, n_pages) pages of one slot.
    The grid is a work list of the live (slot, page group) pairs, ordered
    slot by slot: ``_schedule`` builds it in the wrapper from the lengths,
    and its size, a run-time value, is the number of live groups. A grid
    step costs the pipeline's bookkeeping for each of its operands whether
    or not it computes, so a grid of every slot and every page of the
    table (32 slots x 128 pages per layer call when serving) paid for dead
    slots and pages past each slot's length; now neither is in the grid.
    Dead slots (length 0) are never visited, and the wrapper gives them
    zeros.
  * The online-softmax running state (m, l, acc) lives in VMEM scratch
    persisting across a slot's groups, exactly like the k-block dimension
    of the flash kernel: reset at a slot's first group, written out at its
    last.
  * The P pages of a step are P K operands and P V operands, each the same
    pool array with its own BlockSpec; operand p aims at the group's page
    p. A manual DMA per page from a pool left in HBM (``memory_space=ANY``
    and ``make_async_copy``) would need one operand only, but Mosaic
    refuses its page slice at Hkv=5: "Slice shape along dimension 3 must
    be aligned to tiling (8), but is 5". A BlockSpec whose minor dims are
    the pool's whole (Hkv, Dh) is what the tiling allows.
  * The block table, the lengths and the work list are **scalar-prefetch**
    operands (``pltpu.PrefetchScalarGridSpec``): each K/V index_map reads
    ``tables[b, j * P + p]`` to aim its page DMA at a physical block, so
    only the pages a slot actually owns are ever pulled from HBM.
  * One page DMA carries every KV head: the K/V block is
    ``(1, block_size, 1, Hkv, Dh)``, whose two minor dims are the full pool
    dims — the TPU tiling rule (minor dims divisible by (8, 128) or equal to
    the array's) refuses a one-head block once Hkv is not a multiple of 8
    (smollm-360m: Hkv=5), and Mosaic cannot prove a grid-indexed head offset
    aligned.
  * All heads in two matmuls: a page's block holds one (Hkv, Dh) tile per
    token, so the step pads the heads to a whole sublane tile (``hp``) and
    reads the group as ``[tokens * hp, Dh]`` rows of (token, head) pairs.
    Every query row scores against every such row; a mask keeps the rows of
    its own KV head. Pulling one head's [tokens, Dh] out of the tiles
    instead cost a relayout per head and page, which bounded the kernel.
  * In a slot's last group, the operands past its last valid page re-read
    that page (a table narrower than a whole number of groups clamps the
    same way). A positional ``pos < length`` mask hides their columns and
    the unwritten tail of the last page, which is what keeps trash-block
    garbage (unallocated table entries) out of every result.
  * GQA is native: each KV head's ``G = H // Hkv`` grouped query heads are
    scored against the loaded pages, so grouped configs serve without
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
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# pages one grid step carries (fewer when the table is narrower)
MAX_PAGES_PER_STEP = 8


def pages_per_step(n_pages: int) -> int:
    """P: the pages one grid step walks for a table ``n_pages`` wide."""
    return max(min(MAX_PAGES_PER_STEP, n_pages), 1)


def grid_steps(lengths, n_pages: int, block_size: int):
    """What the kernel's grid walks, counted on the host.

    ``lengths`` holds the per-slot ``lengths`` operand of one call, or a
    stack of them (any leading axes, slots last). Returns ``(steps,
    live_steps, live_pages)`` summed over the calls: the grid steps (a call
    with no live slot still takes one), those that compute, and the pages
    below each slot's length that they carry."""
    lengths = np.asarray(lengths, np.int64)
    lengths = lengths.reshape(-1, lengths.shape[-1])
    P = pages_per_step(n_pages)
    groups = np.minimum(-(-lengths // (P * block_size)), -(-n_pages // P))
    live_steps = groups.sum(axis=-1)
    steps = int(np.maximum(live_steps, 1).sum())
    pages = np.minimum(-(-lengths // block_size), n_pages)
    return steps, int(live_steps.sum()), int(pages.sum())


def _schedule(lengths, n_pages: int, block_size: int):
    """The work list: for grid step ``i``, its slot and the page group
    within that slot, over the live groups in slot order; and the number
    of steps, at least 1 (a call with no live slot runs one step, on a
    dead slot, that computes nothing)."""
    B = lengths.shape[0]
    P = pages_per_step(n_pages)
    n_groups = pl.cdiv(n_pages, P)
    groups = jnp.minimum(-(-lengths // (P * block_size)), n_groups)
    ends = jnp.cumsum(groups)
    step = jnp.arange(B * n_groups, dtype=jnp.int32)
    slot = jnp.minimum(jnp.sum(step[:, None] >= ends[None, :], axis=1),
                       B - 1).astype(jnp.int32)
    group = step - (ends[slot] - groups[slot])
    return slot, group.astype(jnp.int32), jnp.maximum(ends[-1], 1)


def _paged_kernel(tables_ref, lengths_ref, layer_ref, slot_ref, group_ref,
                  q_ref, *refs, scale: float, block_size: int, pages: int,
                  n_groups: int, q_len: int, group: int, n_kv_heads: int):
    """Q*G query rows per KV head: the q block's row axis is (KV head,
    window position, grouped query head), so row r belongs to head
    r // (Q*G) at window position (r % (Q*G)) // G. Single-token decode is
    the Q=1 case, whose per-row causal mask reduces to ``pos < length``.

    ``refs`` holds the ``pages`` K blocks of this grid step, then its
    ``pages`` V blocks, then the output block and the three scratches."""
    k_refs, v_refs = refs[:pages], refs[pages:2 * pages]
    o_ref, m_scr, l_scr, acc_scr = refs[2 * pages:]
    i = pl.program_id(0)
    b = slot_ref[i]
    j = group_ref[i]
    length = lengths_ref[b]
    span = pages * block_size              # KV positions one step covers
    hp = pl.cdiv(n_kv_heads, 8) * 8        # heads padded to a sublane tile
    rows = q_len * group                   # query rows per KV head

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def tokens_by_head(page_refs):
        """The group's pages as [span * hp, Dh] rows, (token, head) order."""
        x = jnp.concatenate([r[0, :, 0].astype(jnp.float32)
                             for r in page_refs], axis=0)   # [span, Hkv, Dh]
        if hp != n_kv_heads:
            x = jnp.concatenate([x, jnp.zeros(
                (span, hp - n_kv_heads, x.shape[-1]), jnp.float32)], axis=1)
        return x.reshape(span * hp, x.shape[-1])

    # the one step of a call with no live slot lands on a dead slot
    @pl.when(j * span < length)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale            # [Hkv*rows, Dh]
        k, v = tokens_by_head(k_refs), tokens_by_head(v_refs)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)

        # a row reads its own KV head's columns, and, at window position
        # w, positions < length - (Q - 1 - w); that also hides the tail
        # group's re-read pages
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        pos = j * span + col // hp
        window = (row % rows) // group
        ok = (col % hp == row // rows) & (pos < length - (q_len - 1 - window))
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_scr[...]                                 # [Hkv*rows, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(((j + 1) * span >= length) | (j == n_groups - 1))
    def _finalize():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)   # fully-masked row: zeros, not NaN
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def _grid_spec(n_steps, n_pages, heads_rows, Hkv, Dh, block_size):
    """Grid (work-list step) over q/o blocks of one slot's ``[Hkv * rows,
    Dh]`` and, per page of the step's group, one K and one V block of that
    page's ``[block_size, 1, Hkv, Dh]``."""
    P = pages_per_step(n_pages)

    def kv_map(p):
        def index(i, tables, lengths, layer, slot, group):
            # operands past the slot's last valid page re-read it
            b = slot[i]
            last = jnp.maximum(lengths[b] - 1, 0) // block_size
            page = jnp.minimum(group[i] * P + p,
                               jnp.minimum(last, n_pages - 1))
            return (tables[b, page], 0, layer[0], 0, 0)
        return index

    def q_map(i, tables, lengths, layer, slot, group):
        return (slot[i], 0, 0)

    kv_specs = [pl.BlockSpec((1, block_size, 1, Hkv, Dh), kv_map(p))
                for p in range(P)]
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(n_steps,),
        in_specs=[pl.BlockSpec((1, heads_rows, Dh), q_map),
                  *kv_specs, *kv_specs],
        out_specs=pl.BlockSpec((1, heads_rows, Dh), q_map),
        scratch_shapes=[
            pltpu.VMEM((heads_rows, 1), jnp.float32),    # running max m
            pltpu.VMEM((heads_rows, 1), jnp.float32),    # running denom l
            pltpu.VMEM((heads_rows, Dh), jnp.float32),   # fp32 accumulator
        ],
    )


def _prepare(q3, k_pages, v_pages, tables, lengths, layer, q_len, group):
    """What both wrappers hand ``pl.pallas_call``: the kernel, its grid spec
    and its operands. q3 is ``[B, Hkv * Q * G, Dh]`` in (KV head, window
    position, grouped head) row order."""
    B, heads_rows, Dh = q3.shape
    block_size, Hkv = k_pages.shape[1], k_pages.shape[3]
    n_pages = tables.shape[1]
    P = pages_per_step(n_pages)
    slot, grp, n_steps = _schedule(lengths, n_pages, block_size)
    kernel = functools.partial(
        _paged_kernel, scale=Dh ** -0.5, block_size=block_size, pages=P,
        n_groups=pl.cdiv(n_pages, P), q_len=q_len, group=group,
        n_kv_heads=Hkv)
    grid_spec = _grid_spec(n_steps, n_pages, heads_rows, Hkv, Dh, block_size)
    args = (tables.astype(jnp.int32), lengths,
            jnp.asarray(layer, jnp.int32).reshape(1), slot, grp, q3,
            *[k_pages] * P, *[v_pages] * P)
    return kernel, grid_spec, args


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
    :func:`paged_attention` with each KV head's rows widened from G to Q*G.
    """
    B, Q, H, Dh = q.shape
    Hkv = k_pages.shape[3]
    assert H % Hkv == 0, (H, Hkv)
    G = H // Hkv
    # [B, Q, Hkv, G, Dh] -> [B, Hkv * Q * G, Dh]: rows ordered (KV head,
    # window position, grouped head)
    q3 = q.reshape(B, Q, Hkv, G, Dh).transpose(0, 2, 1, 3, 4).reshape(
        B, Hkv * Q * G, Dh)
    lengths = lengths.astype(jnp.int32)
    kernel, grid_spec, args = _prepare(q3, k_pages, v_pages, tables, lengths,
                                       layer, Q, G)
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q3.shape, q3.dtype),
        interpret=interpret)(*args)
    # the grid never visits a dead slot's output block
    out = jnp.where((lengths > 0)[:, None, None], out, 0)
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
    H, Hkv = q.shape[1], k_pages.shape[3]
    assert H % Hkv == 0, (H, Hkv)
    lengths = lengths.astype(jnp.int32)
    # the H query heads are already (KV head, grouped head) ordered
    kernel, grid_spec, args = _prepare(q, k_pages, v_pages, tables, lengths,
                                       layer, 1, H // Hkv)
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret)(*args)
    # the grid never visits a dead slot's output block
    return jnp.where((lengths > 0)[:, None, None], out, 0)
