"""Pallas kernel sweeps (interpret=True on CPU) vs pure-jnp oracles."""
import functools

import jax
import jax.numpy as jnp
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    # Without hypothesis the @given properties degrade to a fixed 3-point
    # spot check so the parity sweeps in this file still run in pip-less
    # environments; CI installs hypothesis and gets the full search.
    class st:  # noqa: N801 - mimics hypothesis.strategies
        @staticmethod
        def integers(lo, hi):
            return (lo, (lo + hi) // 2, hi)

    def settings(**_kw):
        return lambda f: f

    def given(**kw):
        def deco(f):
            # no functools.wraps: pytest must see a zero-arg signature, not
            # f's `seed` parameter (which it would treat as a fixture)
            def run():
                for vals in zip(*kw.values()):
                    f(**dict(zip(kw.keys(), vals)))
            run.__name__ = f.__name__
            run.__doc__ = f.__doc__
            return run
        return deco

from repro.kernels import (attention_ref, flash_attention,
                           paged_attention_pallas, paged_attention_ref,
                           rglru_ref, rglru_scan)

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _qkv(key, B, H, Hkv, S, D, dtype):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, H, S, D)).astype(dtype)
    k = jax.random.normal(ks[1], (B, Hkv, S, D)).astype(dtype)
    v = jax.random.normal(ks[2], (B, Hkv, S, D)).astype(dtype)
    return q, k, v


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,Hkv,S,D,bq,bk", [
    (1, 2, 2, 128, 64, 64, 64),      # MHA
    (2, 4, 2, 256, 64, 128, 128),    # GQA 2:1
    (1, 8, 1, 128, 128, 128, 64),    # MQA, Dh=128
])
def test_flash_causal_sweep(dtype, B, H, Hkv, S, D, bq, bk):
    q, k, v = _qkv(jax.random.PRNGKey(0), B, H, Hkv, S, D, dtype)
    out = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                          interpret=True)
    ref = attention_ref(q, k, v, causal=True)
    err = jnp.max(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32)))
    assert float(err) < TOL[dtype], float(err)


@pytest.mark.parametrize("window", [32, 64, 100])
def test_flash_sliding_window(window):
    q, k, v = _qkv(jax.random.PRNGKey(1), 1, 2, 2, 256, 64, jnp.float32)
    out = flash_attention(q, k, v, causal=True, window=window,
                          block_q=64, block_k=64, interpret=True)
    ref = attention_ref(q, k, v, causal=True, window=window)
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-5


def test_flash_noncausal():
    q, k, v = _qkv(jax.random.PRNGKey(2), 1, 2, 2, 128, 64, jnp.float32)
    out = flash_attention(q, k, v, causal=False, block_q=64, block_k=64,
                          interpret=True)
    ref = attention_ref(q, k, v, causal=False)
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-5


def test_flash_block_pruning_equivalence():
    """Different block shapes give identical results (pruning is mask-safe)."""
    q, k, v = _qkv(jax.random.PRNGKey(3), 1, 2, 1, 256, 64, jnp.float32)
    a = flash_attention(q, k, v, causal=True, window=64, block_q=64,
                        block_k=64, interpret=True)
    b = flash_attention(q, k, v, causal=True, window=64, block_q=128,
                        block_k=32, interpret=True)
    assert float(jnp.max(jnp.abs(a - b))) < 2e-5


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,R,chunk,br", [
    (1, 128, 128, 64, 128),
    (2, 256, 256, 128, 128),
    (1, 512, 384, 256, 128),
])
def test_rglru_scan_sweep(dtype, B, S, R, chunk, br):
    key = jax.random.PRNGKey(4)
    a = jax.nn.sigmoid(jax.random.normal(key, (B, S, R))).astype(dtype)
    x = jax.random.normal(jax.random.fold_in(key, 1), (B, S, R)).astype(dtype)
    out = rglru_scan(a, x, chunk=chunk, block_r=br, interpret=True)
    ref = rglru_ref(a, x)
    err = jnp.max(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32)))
    assert float(err) < (5e-2 if dtype == jnp.bfloat16 else 1e-4), float(err)


# ---------------------------------------------------------------------------
# Paged decode attention: block-table walk vs gather-everything oracle
# ---------------------------------------------------------------------------

def _paged_case(key, B, H, Hkv, Dh, L, block_size, n_pages, num_blocks,
                lengths, dtype=jnp.float32, rng_seed=0):
    """Random pool + a valid slot/table assignment for the given lengths.

    Tables are deliberately permuted/non-contiguous: each slot's pages come
    from a shuffled pool order, and unused entries point at the trash block
    (index ``num_blocks``) exactly as ``BlockAllocator`` pads them."""
    import numpy as np
    ks = jax.random.split(key, 3)
    kp = jax.random.normal(ks[0], (num_blocks + 1, block_size, L, Hkv, Dh)
                           ).astype(dtype)
    vp = jax.random.normal(ks[1], (num_blocks + 1, block_size, L, Hkv, Dh)
                           ).astype(dtype)
    q = jax.random.normal(ks[2], (B, H, Dh)).astype(dtype)
    rng = np.random.default_rng(rng_seed)
    order = rng.permutation(num_blocks)
    tables = np.full((B, n_pages), num_blocks, np.int32)   # trash-padded
    nxt = 0
    for i, n in enumerate(lengths):
        used = -(-n // block_size)
        tables[i, :used] = order[nxt:nxt + used]
        nxt += used
    assert nxt <= num_blocks, "case needs a bigger pool"
    return q, kp, vp, jnp.asarray(tables), jnp.asarray(lengths, jnp.int32)


@pytest.mark.parametrize("block_size", [8, 16, 128])
def test_paged_parity_block_sizes(block_size):
    """Kernel (interpret) vs oracle across block sizes, ragged lengths
    hitting every tail-offset class (0, 1, bs-1, bs, bs+1 past a boundary),
    a dead slot, and non-contiguous tables."""
    bs = block_size
    lengths = [1, bs - 1, bs, bs + 1, 0, 3 * bs + bs // 2]
    B = len(lengths)
    n_pages = -(-max(lengths) // bs)
    num_blocks = sum(-(-n // bs) for n in lengths) + 2
    q, kp, vp, tables, lens = _paged_case(
        jax.random.PRNGKey(bs), B, 4, 2, 16, 2, bs, n_pages, num_blocks,
        lengths)
    for layer in range(2):
        out = paged_attention_pallas(q, kp, vp, tables, lens, layer,
                                     interpret=True)
        ref = paged_attention_ref(q, kp, vp, tables, lens, layer)
        assert float(jnp.max(jnp.abs(out - ref))) < TOL[jnp.float32]
        assert bool(jnp.all(jnp.isfinite(out)))


@pytest.mark.parametrize("H,Hkv", [(2, 2), (4, 2), (8, 1)])
def test_paged_parity_gqa(H, Hkv):
    """GQA head grouping: MHA, 2:1 grouped, and MQA all match the oracle."""
    lengths = [5, 17, 32, 0]
    q, kp, vp, tables, lens = _paged_case(
        jax.random.PRNGKey(7 * H + Hkv), len(lengths), H, Hkv, 32, 3,
        8, 4, 12, lengths)
    out = paged_attention_pallas(q, kp, vp, tables, lens, 1, interpret=True)
    ref = paged_attention_ref(q, kp, vp, tables, lens, 1)
    assert float(jnp.max(jnp.abs(out - ref))) < TOL[jnp.float32]


def test_paged_dead_slot_emits_zeros():
    """A dead slot (length 0, table all-trash) must emit exactly zeros — the
    l==0 guard, not NaN from a fully-masked softmax."""
    lengths = [0, 0, 9]
    q, kp, vp, tables, lens = _paged_case(
        jax.random.PRNGKey(9), 3, 2, 2, 16, 1, 8, 2, 4, lengths)
    out = paged_attention_pallas(q, kp, vp, tables, lens, 0, interpret=True)
    assert float(jnp.abs(out[:2]).max()) == 0.0
    assert float(jnp.abs(out[2]).max()) > 0.0
    # a call with no live slot at all
    out = paged_attention_pallas(q, kp, vp, tables, lens * 0, 0,
                                 interpret=True)
    assert float(jnp.abs(out).max()) == 0.0


@pytest.mark.parametrize("n_pages", [1, 2, 4, 8, 16, 64])
def test_paged_parity_pages_per_step(n_pages):
    """A grid step walks P = min(8, n_pages) pages: P clamped below 8 and
    full, lengths one short of, at and one past a group (P * bs), a full
    table, dead slots, and table entries past each length aimed at a
    poisoned trash block, all match the oracle; dead slots emit zeros."""
    import numpy as np
    from repro.kernels.paged_attention import pages_per_step
    bs = 8
    group = pages_per_step(n_pages) * bs
    lengths = [n for n in (group - 1, group, group + 1, n_pages * bs)
               if n <= n_pages * bs] + [0, 0]
    B = len(lengths)
    num_blocks = sum(-(-n // bs) for n in lengths) + 1
    q, kp, vp, tables, lens = _paged_case(
        jax.random.PRNGKey(n_pages), B, 4, 2, 16, 2, bs, n_pages, num_blocks,
        lengths, rng_seed=n_pages)
    kp = kp.at[num_blocks].set(1e4)
    vp = vp.at[num_blocks].set(-1e4)
    out = paged_attention_pallas(q, kp, vp, tables, lens, 1, interpret=True)
    ref = paged_attention_ref(q, kp, vp, tables, lens, 1)
    assert (np.asarray(tables)[np.asarray(lens) == 0] == num_blocks).all()
    assert float(jnp.max(jnp.abs(out - ref))) < TOL[jnp.float32]
    assert float(jnp.abs(out[-2:]).max()) == 0.0


@settings(max_examples=12, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_paged_layout_invariance(seed):
    """Property: the output is invariant to the physical block-table layout.

    The same logical K/V content placed under two random physical layouts
    (different block order in the pool) must produce bit-identical outputs:
    the walk visits pages in logical order regardless of where they live, so
    the online-softmax reduction order — and hence every float — is equal."""
    import numpy as np
    rng = np.random.default_rng(seed)
    bs, L, Hkv, Dh, B = 8, 2, 2, 16, 3
    lengths = [int(rng.integers(0, 25)) for _ in range(B)]
    n_pages = max(-(-max(lengths) // bs), 1)
    used = sum(-(-n // bs) for n in lengths)
    num_blocks = used + 3
    key = jax.random.PRNGKey(seed)
    # logical content: per slot, a dense [n_pages*bs] K/V stream
    k_log = jax.random.normal(key, (B, n_pages * bs, L, Hkv, Dh))
    v_log = jax.random.normal(jax.random.fold_in(key, 1),
                              (B, n_pages * bs, L, Hkv, Dh))
    q = jax.random.normal(jax.random.fold_in(key, 2), (B, 4, Dh))

    def place(layout_seed):
        r = np.random.default_rng(layout_seed)
        order = r.permutation(num_blocks)
        kp = np.array(jax.random.normal(
            jax.random.fold_in(key, 3 + layout_seed),
            (num_blocks + 1, bs, L, Hkv, Dh)))  # garbage background (copy)
        vp = kp[::-1].copy()
        tables = np.full((B, n_pages), num_blocks, np.int32)
        nxt = 0
        for i, n in enumerate(lengths):
            for j in range(-(-n // bs)):
                blk = order[nxt]; nxt += 1
                tables[i, j] = blk
                kp[blk] = np.asarray(k_log[i, j * bs:(j + 1) * bs])
                vp[blk] = np.asarray(v_log[i, j * bs:(j + 1) * bs])
        return (jnp.asarray(kp, jnp.float32), jnp.asarray(vp, jnp.float32),
                jnp.asarray(tables))

    lens = jnp.asarray(lengths, jnp.int32)
    outs = []
    for layout_seed in (0, 1):
        kp, vp, tables = place(layout_seed)
        outs.append(paged_attention_pallas(q.astype(jnp.float32), kp, vp,
                                           tables, lens, 1, interpret=True))
    assert bool(jnp.all(outs[0] == outs[1])), "layout changed the bits"


@settings(max_examples=12, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_paged_aliased_tables_invariance(seed):
    """Property: cross-slot aliasing is invisible to the block walk.

    Shared-prefix copy-on-write makes several slots' tables point at the
    same physical page. The kernel only ever reads through tables[i], so a
    pool where the common prefix pages are stored once and aliased must
    produce bit-identical outputs to a pool where every slot holds a
    private copy of the same logical content — for the Pallas walk and the
    gather oracle alike."""
    import numpy as np
    rng = np.random.default_rng(seed)
    bs, L, Hkv, Dh, B = 8, 2, 2, 16, 3
    n_shared = int(rng.integers(1, 3))           # full prefix pages shared
    tails = [int(rng.integers(0, 10)) for _ in range(B)]
    lengths = [n_shared * bs + t for t in tails]
    n_pages = -(-max(lengths) // bs)
    key = jax.random.PRNGKey(seed)
    k_log = jax.random.normal(key, (B, n_pages * bs, L, Hkv, Dh))
    v_log = jax.random.normal(jax.random.fold_in(key, 1),
                              (B, n_pages * bs, L, Hkv, Dh))
    # every slot sees the same logical prefix content
    k_log = k_log.at[:, :n_shared * bs].set(k_log[0, :n_shared * bs])
    v_log = v_log.at[:, :n_shared * bs].set(v_log[0, :n_shared * bs])
    q = jax.random.normal(jax.random.fold_in(key, 2), (B, 4, Dh))

    def place(aliased):
        num_blocks = B * n_pages + n_shared + 2
        kp = np.array(jax.random.normal(
            jax.random.fold_in(key, 3 + int(aliased)),
            (num_blocks + 1, bs, L, Hkv, Dh)))   # garbage background
        vp = kp[::-1].copy()
        tables = np.full((B, n_pages), num_blocks, np.int32)
        nxt = 0
        shared_run = None
        for i, n in enumerate(lengths):
            for j in range(-(-n // bs)):
                if aliased and j < n_shared and shared_run is not None:
                    tables[i, j] = shared_run[j]  # alias slot 0's page
                    continue
                blk = nxt; nxt += 1
                tables[i, j] = blk
                kp[blk] = np.asarray(k_log[i, j * bs:(j + 1) * bs])
                vp[blk] = np.asarray(v_log[i, j * bs:(j + 1) * bs])
            if aliased and shared_run is None:
                shared_run = [int(t) for t in tables[i, :n_shared]]
        return (jnp.asarray(kp, jnp.float32), jnp.asarray(vp, jnp.float32),
                jnp.asarray(tables))

    lens = jnp.asarray(lengths, jnp.int32)
    qf = q.astype(jnp.float32)
    outs = {}
    for aliased in (False, True):
        kp, vp, tables = place(aliased)
        outs[aliased] = (
            paged_attention_pallas(qf, kp, vp, tables, lens, 1,
                                   interpret=True),
            paged_attention_ref(qf, kp, vp, tables, lens, 1))
    assert bool(jnp.all(outs[True][0] == outs[False][0])), \
        "aliased tables changed the Pallas walk's bits"
    assert bool(jnp.all(outs[True][1] == outs[False][1])), \
        "aliased tables changed the oracle's bits"


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_rglru_decay_bounded_state(seed):
    """Property: with a ∈ (0,1) and bounded inputs, the recurrence state is
    bounded by |x|_max / (1 - a_max) — no blow-up."""
    key = jax.random.PRNGKey(seed)
    a = jax.nn.sigmoid(jax.random.normal(key, (1, 64, 128))) * 0.98
    x = jnp.clip(jax.random.normal(jax.random.fold_in(key, 1), (1, 64, 128)),
                 -3, 3)
    h = rglru_scan(a, x, chunk=32, block_r=128, interpret=True)
    bound = 3.0 / (1.0 - float(a.max())) + 1e-3
    assert float(jnp.abs(h).max()) <= bound
