"""Block-native paged decode (`kv_impl="kernel"` / `"pallas"`) vs the jnp
reference serving path and the serial one-request oracle.

The kernel path changes the attention *implementation* (online softmax over
block-table pages, fused tail append) but not the computation's semantics:
the contract is bitwise-or-tolerance — per-request greedy token streams must
be identical to the reference path (and hence to serial decode) in every
mode, including under forced preemption; logits agree to kernel tolerance
rather than bitwise because the blocked softmax reassociates reductions.

`kv_impl="kernel"` on CPU runs the block-native step with the jnp-gather
attention oracle (exercising the fused append + batched layer scan);
`kv_impl="pallas"` forces the actual Pallas kernel in interpret mode — the
CPU CI stand-in for the compiled TPU path.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get
from repro.models import decode_step, init_params, prefill
from repro.serve import ServeEngine


@pytest.fixture(scope="module")
def model():
    cfg = get("smollm-360m").reduced().with_overrides(
        d_model=32, n_heads=2, n_kv_heads=2, head_dim=16, d_ff=64, vocab=64)
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _serial_greedy(cfg, params, prompt, max_new, eos_id=None, capacity=32):
    """Reference: one-request-at-a-time prefill + decode_step loop."""
    lg, cache = prefill(cfg, params,
                        jnp.asarray(np.asarray(prompt, np.int32)[None]),
                        capacity)
    tok = int(jnp.argmax(lg[0, -1]))
    out = [tok]
    while len(out) < max_new and (eos_id is None or tok != eos_id):
        lg, cache = decode_step(cfg, params,
                                jnp.asarray([[tok]], jnp.int32), cache)
        tok = int(jnp.argmax(lg[0, -1]))
        out.append(tok)
    return out


def _drain(cfg, params, reqs, kv_impl, **kw):
    kw.setdefault("capacity", 32)
    kw.setdefault("max_batch", 3)
    kw.setdefault("decode_chunk", 3)
    kw.setdefault("block_size", 4)
    eng = ServeEngine(cfg, params, mode="paged", kv_impl=kv_impl, **kw)
    rids = [eng.submit(p, max_new_tokens=b) for p, b in reqs]
    results = eng.run()
    return eng, [results[r] for r in rids]


def test_kv_impl_validation_and_auto(model):
    cfg, params = model
    with pytest.raises(ValueError, match="kv_impl"):
        ServeEngine(cfg, params, mode="paged", capacity=32, max_batch=2,
                    kv_impl="gpu")
    eng = ServeEngine(cfg, params, mode="paged", capacity=32, max_batch=2,
                      kv_impl="auto")
    # auto resolves by backend: the compiled kernel only on TPU, the bitwise
    # reference path everywhere else (this suite runs on CPU)
    expect = "kernel" if jax.default_backend() == "tpu" else "reference"
    assert eng.kv_impl == expect
    assert ServeEngine(cfg, params, capacity=32, max_batch=2).kv_impl is None


def test_kernel_streams_match_serial(model):
    """Mid-decode admission workload: the block-native path reproduces the
    serial greedy streams token for token."""
    cfg, params = model
    rng = np.random.default_rng(0)
    reqs = [(rng.integers(0, cfg.vocab, size=int(rng.integers(3, 10))),
             int(b)) for b in (4, 7, 1, 5)]
    eng, streams = _drain(cfg, params, reqs, "kernel", max_batch=2)
    for (prompt, budget), got in zip(reqs, streams):
        assert got == _serial_greedy(cfg, params, prompt, budget)
    assert eng.pool.free_blocks == eng.pool.num_blocks


def test_pallas_interpret_streams_match_serial(model):
    """The forced Pallas kernel (interpret mode on CPU — the CI stand-in for
    the compiled TPU path) keeps the same streams."""
    cfg, params = model
    rng = np.random.default_rng(3)
    reqs = [(rng.integers(0, cfg.vocab, size=int(rng.integers(3, 8))),
             int(b)) for b in (4, 6, 3)]
    eng, streams = _drain(cfg, params, reqs, "pallas", capacity=16,
                          num_blocks=16)
    assert eng.kv_impl == "pallas"
    for (prompt, budget), got in zip(reqs, streams):
        assert got == _serial_greedy(cfg, params, prompt, budget, capacity=16)
    assert eng.pool.free_blocks == eng.pool.num_blocks


def test_kernel_preemption_preserves_streams(model):
    """Forced preemption (pool deliberately too small): evicted requests
    restart on the kernel path and still reproduce the serial streams, and
    the pool drains clean."""
    cfg, params = model
    rng = np.random.default_rng(1)
    reqs = [(rng.integers(0, cfg.vocab, size=int(rng.integers(4, 12))),
             int(b)) for b in (9, 8, 10, 7, 9)]
    eng, streams = _drain(cfg, params, reqs, "kernel", max_batch=4,
                          decode_chunk=4, num_blocks=7)
    assert eng.stats["preemptions"] > 0, "workload must exercise preemption"
    for (prompt, budget), got in zip(reqs, streams):
        assert got == _serial_greedy(cfg, params, prompt, budget)
    assert eng.pool.free_blocks == eng.pool.num_blocks


def test_kernel_eos_matches_serial(model):
    """In-scan EOS masking stops a kernel-path stream exactly where serial
    decode stops it."""
    cfg, params = model
    prompt = [5, 9, 2, 7]
    ref = _serial_greedy(cfg, params, prompt, 8)
    k = next(i for i in range(1, len(ref)) if ref[i] not in ref[:i])
    eng, streams = _drain(cfg, params, [(prompt, 8), ([1, 2, 3], 6)],
                          "kernel", max_batch=2, decode_chunk=4,
                          eos_id=ref[k])
    assert streams[0] == ref[:k + 1] and streams[0][-1] == ref[k]
    assert len(streams[1]) <= 6
    assert eng.pool.free_blocks == eng.pool.num_blocks


def test_kernel_moe_per_slot_routing(model):
    """MoE family: the batched kernel step must keep routing per-slot (each
    request's token sees its own expert capacity), so streams still match
    the per-slot-vmapped reference path."""
    cfg = get("phi3.5-moe-42b-a6.6b").reduced().with_overrides(
        d_model=32, n_heads=2, n_kv_heads=2, head_dim=16, d_ff=64, vocab=64)
    params = init_params(cfg, jax.random.PRNGKey(1))
    rng = np.random.default_rng(5)
    reqs = [(rng.integers(0, cfg.vocab, size=int(rng.integers(3, 9))),
             int(b)) for b in (4, 6, 3)]
    _, ref_streams = _drain(cfg, params, reqs, "reference", max_batch=2)
    _, ker_streams = _drain(cfg, params, reqs, "kernel", max_batch=2)
    assert ker_streams == ref_streams


def test_kernel_logits_within_tolerance(model):
    """One decode step, kernel path vs reference path, same pool state: the
    last-layer logits agree to attention-kernel tolerance (the 'or-tolerance'
    half of the contract — reduction order differs, bits may not)."""
    from repro.models.paged import paged_decode_step
    from repro.kernels import ops, paged_attention_ref
    from repro.serve.batch import tail_targets

    cfg, params = model
    rng = np.random.default_rng(7)
    B, bs, n_pages = 3, 4, 4
    L, Hkv, Dh = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
    num_blocks = B * n_pages
    pool_kv = {
        "k": jnp.asarray(rng.normal(size=(num_blocks + 1, bs, L, Hkv, Dh)),
                         jnp.float32),
        "v": jnp.asarray(rng.normal(size=(num_blocks + 1, bs, L, Hkv, Dh)),
                         jnp.float32)}
    tables = jnp.asarray(rng.permutation(num_blocks).reshape(B, n_pages)
                         .astype(np.int32))
    idx = jnp.asarray([3, 7, 11], jnp.int32)
    live = jnp.ones((B,), bool)
    tok = jnp.asarray(rng.integers(0, cfg.vocab, size=B), jnp.int32)
    blk, off = tail_targets(tables, idx, live, bs, num_blocks)
    lengths = (idx + 1).astype(jnp.int32)

    def run(attend):
        return paged_decode_step(cfg, params, tok, pool_kv, tables, blk, off,
                                 idx, lengths, attend=attend)

    ref_logits, _ = run(paged_attention_ref)
    ker_logits, _ = run(
        lambda *a: ops.paged_attention(*a, force_pallas=True, interpret=True))
    assert float(jnp.max(jnp.abs(ref_logits - ker_logits))) < 2e-4
    assert jnp.argmax(ref_logits, -1).tolist() == \
        jnp.argmax(ker_logits, -1).tolist()


# -- multi-token verify window (speculative decoding read path) --------------


def _multi_fixture(rng, *, B, Q, n_pages, bs, Hkv, G, Dh=8, L=2,
                   lengths=None, dead=(), permute=True):
    """Random pool + tables + a Q-row query window per slot.

    lengths are the per-slot valid KV counts AFTER appending the window
    (so live slots need lengths >= Q); ``dead`` slots get length 0. Tables
    are a permutation of the physical blocks by default — the kernel must
    never rely on block contiguity."""
    H = Hkv * G
    num_blocks = B * n_pages
    kp = jnp.asarray(rng.normal(size=(num_blocks + 1, bs, L, Hkv, Dh)),
                     jnp.float32)
    vp = jnp.asarray(rng.normal(size=(num_blocks + 1, bs, L, Hkv, Dh)),
                     jnp.float32)
    order = rng.permutation(num_blocks) if permute else np.arange(num_blocks)
    tables = jnp.asarray(order.reshape(B, n_pages).astype(np.int32))
    if lengths is None:
        lengths = rng.integers(Q, n_pages * bs + 1, size=B)
    lengths = np.asarray(lengths, np.int32)
    lengths[list(dead)] = 0
    q = jnp.asarray(rng.normal(size=(B, Q, H, Dh)), jnp.float32)
    return q, kp, vp, tables, jnp.asarray(lengths)


@pytest.mark.parametrize("Q", [1, 4, 5])  # 5 = block_size + 1: spans blocks
@pytest.mark.parametrize("G", [1, 2])     # MHA and grouped-query
def test_multi_ref_matches_row_by_row_single_ref(Q, G):
    """Semantic anchor for the multi-token oracle: row r of a Q-window at
    total length S must equal a single-token query at length S-(Q-1-r) —
    the fused verify is exactly Q successive decode reads."""
    from repro.kernels import paged_attention_multi_ref, paged_attention_ref

    rng = np.random.default_rng(20 + Q)
    q, kp, vp, tables, lengths = _multi_fixture(
        rng, B=3, Q=Q, n_pages=3, bs=4, Hkv=2, G=G,
        lengths=[Q, Q + 3, 12], dead=())
    out = paged_attention_multi_ref(q, kp, vp, tables, lengths, layer=1)
    for r in range(Q):
        row = paged_attention_ref(q[:, r], kp, vp, tables,
                                  lengths - (Q - 1 - r), layer=1)
        np.testing.assert_allclose(np.asarray(out[:, r]), np.asarray(row),
                                   atol=1e-6, rtol=1e-6)


@pytest.mark.parametrize("Q", [1, 4, 5])
@pytest.mark.parametrize("G", [1, 2])
def test_multi_kernel_parity_interpret(Q, G):
    """Pallas multi-token kernel (interpret mode) vs the gather oracle
    `paged_attention_multi_ref`, across window sizes (1, mid-block,
    block-spanning), GQA ratios, permuted tables and a dead slot, with
    every tail-offset class in the lengths mix."""
    from repro.kernels import paged_attention_multi, paged_attention_multi_ref
    from repro.kernels.paged_attention import (
        paged_attention_multi as multi_kernel)

    rng = np.random.default_rng(40 + Q + 10 * G)
    bs = 4
    # offsets 0 (block-aligned), mid-block, and full-pool tail
    q, kp, vp, tables, lengths = _multi_fixture(
        rng, B=4, Q=Q, n_pages=3, bs=bs, Hkv=2, G=G,
        lengths=[bs * 2, bs * 2 + 1, Q + 1, bs * 3], dead=(2,))
    want = paged_attention_multi_ref(q, kp, vp, tables, lengths, layer=0)
    got = multi_kernel(q, kp, vp, tables, lengths, layer=0, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    assert (np.asarray(got[2]) == 0).all()  # dead slot zeros out
    # the policy wrapper's forced-pallas route hits the same kernel
    via_ops = paged_attention_multi(q, kp, vp, tables, lengths, layer=0,
                                    force_pallas=True, interpret=True)
    np.testing.assert_allclose(np.asarray(via_ops), np.asarray(got),
                               atol=0, rtol=0)


def test_multi_kernel_q1_degenerates_to_single_token():
    """Q=1 is exactly the single-token decode read: both the oracle and the
    interpret-mode kernel must agree with `paged_attention_ref` (and its
    kernel) on the same pool state."""
    from repro.kernels import paged_attention_multi_ref, paged_attention_ref
    from repro.kernels.paged_attention import (
        paged_attention_multi as multi_kernel)

    rng = np.random.default_rng(9)
    q, kp, vp, tables, lengths = _multi_fixture(
        rng, B=3, Q=1, n_pages=2, bs=4, Hkv=2, G=2, dead=(1,))
    single = paged_attention_ref(q[:, 0], kp, vp, tables, lengths)
    multi = paged_attention_multi_ref(q, kp, vp, tables, lengths)
    np.testing.assert_allclose(np.asarray(multi[:, 0]), np.asarray(single),
                               atol=1e-6, rtol=1e-6)
    ker = multi_kernel(q, kp, vp, tables, lengths, interpret=True)
    np.testing.assert_allclose(np.asarray(ker[:, 0]), np.asarray(single),
                               atol=2e-5, rtol=2e-5)


def test_multi_kernel_ignores_garbage_past_row_lengths():
    """Write-then-mask discipline: K/V past each row's causal length —
    rejected speculative garbage included — must not leak into any output
    row. Poisoning every position >= lengths with huge values changes
    nothing."""
    from repro.kernels import paged_attention_multi_ref
    from repro.kernels.paged_attention import (
        paged_attention_multi as multi_kernel)

    rng = np.random.default_rng(13)
    bs, n_pages = 4, 3
    q, kp, vp, tables, lengths = _multi_fixture(
        rng, B=2, Q=3, n_pages=n_pages, bs=bs, Hkv=2, G=1,
        lengths=[5, 9])
    clean_ref = paged_attention_multi_ref(q, kp, vp, tables, lengths)
    clean_ker = multi_kernel(q, kp, vp, tables, lengths, interpret=True)
    kp_np, vp_np = np.array(kp), np.array(vp)
    tb = np.asarray(tables)
    for b in range(2):
        for pos in range(int(lengths[b]), n_pages * bs):
            blk = tb[b, pos // bs]
            kp_np[blk, pos % bs] = 1e6
            vp_np[blk, pos % bs] = -1e6
    kp2, vp2 = jnp.asarray(kp_np), jnp.asarray(vp_np)
    np.testing.assert_array_equal(
        np.asarray(paged_attention_multi_ref(q, kp2, vp2, tables, lengths)),
        np.asarray(clean_ref))
    np.testing.assert_array_equal(
        np.asarray(multi_kernel(q, kp2, vp2, tables, lengths,
                                interpret=True)),
        np.asarray(clean_ker))


# -- several pages per grid step ----------------------------------------------


@pytest.mark.parametrize("Q", [1, 4])
@pytest.mark.parametrize("n_pages", [1, 2, 4, 8, 16, 64])
def test_multi_kernel_parity_pages_per_step(n_pages, Q):
    """The verify window through a grid of P = min(8, n_pages) pages a step:
    lengths one short of, at and one past a group (P * bs), a full table, a
    dead slot, and table entries past each length aimed at a poisoned trash
    block match the oracle; the dead slot emits zeros."""
    from repro.kernels import paged_attention_multi_ref
    from repro.kernels.paged_attention import (
        pages_per_step, paged_attention_multi as multi_kernel)

    rng = np.random.default_rng(60 + n_pages + Q)
    bs, Hkv, G, Dh, L = 8, 2, 2, 8, 2
    group = pages_per_step(n_pages) * bs
    lengths = [n for n in (group - 1, group, group + 1, n_pages * bs)
               if n <= n_pages * bs] + [0]
    B = len(lengths)
    used = [-(-n // bs) for n in lengths]
    trash = sum(used)
    kp = np.asarray(rng.normal(size=(trash + 1, bs, L, Hkv, Dh)), np.float32)
    vp = np.asarray(rng.normal(size=(trash + 1, bs, L, Hkv, Dh)), np.float32)
    kp[trash], vp[trash] = 1e4, -1e4
    order = rng.permutation(trash)
    tables = np.full((B, n_pages), trash, np.int32)
    for i, n in enumerate(used):
        tables[i, :n] = order[sum(used[:i]):sum(used[:i]) + n]
    q = jnp.asarray(rng.normal(size=(B, Q, Hkv * G, Dh)), jnp.float32)
    args = (q, jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(tables),
            jnp.asarray(lengths, jnp.int32))
    want = paged_attention_multi_ref(*args, layer=1)
    got = multi_kernel(*args, layer=1, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    assert (np.asarray(got[-1]) == 0).all()


@pytest.mark.parametrize("n_pages", [1, 3, 8, 16, 64])
def test_grid_steps_matches_a_walk_of_the_grid(n_pages):
    """The host count of the kernel's grid equals a walk of its work list:
    each slot's page groups that start below its length, one step for a
    call with none, and the pages below the length those steps carry."""
    from repro.kernels.paged_attention import grid_steps, pages_per_step

    bs = 4
    P = pages_per_step(n_pages)
    rng = np.random.default_rng(n_pages)
    lengths = rng.integers(0, n_pages * bs + 1, size=(3, 5))
    lengths[0, 0] = 0
    lengths[1] = 0                     # a call with no live slot
    steps = live_steps = live_pages = 0
    for call in lengths:
        work = [(n, j) for n in call for j in range(-(-n_pages // P))
                if j * P * bs < n]
        steps += max(len(work), 1)
        live_steps += len(work)
        live_pages += sum((j * P + p) * bs < n for n, j in work
                          for p in range(P))
    assert grid_steps(lengths, n_pages, bs) == (steps, live_steps,
                                                live_pages)


def test_engine_counts_the_paged_grid(model):
    """The decode loop's grid counters: with no EOS and no preemption, a
    request of prompt p and budget n runs n - 1 decode steps at lengths
    p + 1 .. p + n - 1, once per layer."""
    cfg, params = model
    rng = np.random.default_rng(11)
    reqs = [(rng.integers(0, cfg.vocab, size=int(n)), int(b))
            for n, b in ((3, 9), (13, 4), (6, 12))]
    eng, _ = _drain(cfg, params, reqs, "kernel", max_batch=2,
                    decode_chunk=3, block_size=4)
    st, bs, L = eng.stats, eng.block_size, cfg.n_layers
    assert st["preemptions"] == 0
    pages = sum(-(-t // bs) for p, n in reqs
                for t in range(len(p) + 1, len(p) + n))
    assert st["paged_live_pages"] == pages * L
    assert 0 < st["paged_live_grid_steps"] <= st["paged_live_pages"]
    # a kernel call with no live slot (its requests done mid-chunk) takes
    # one grid step
    assert (st["paged_live_grid_steps"] <= st["paged_grid_steps"]
            <= st["paged_live_grid_steps"] + st["decode_steps"] * L)
    ref, _ = _drain(cfg, params, reqs, "reference", max_batch=2,
                    decode_chunk=3, block_size=4)
    assert "paged_grid_steps" not in ref.stats
