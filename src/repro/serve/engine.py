"""Serving engine: slot-based continuous batching and paged KV over a
scan-fused decode.

Three modes behind the same ``submit``/``run``/``stream`` API:

* ``mode="paged"`` — the paged-KV path. A
  :class:`~repro.serve.batch.BlockPool` owns the physical
  ``(num_blocks, block_size, ...)`` KV cache; each request holds only the
  blocks its tokens actually occupy, mapped through a per-slot block table.
  Admission is gated on *free blocks* (KV HBM in use), not on slot count, so
  a mixed-length workload admits far more concurrent requests at equal HBM
  than the uniform-reservation modes; when a decode chunk would exhaust the
  pool, the youngest request is preempted back to the queue front and
  restarts later (greedy decode regenerates its stream bit-for-bit). Prefill
  writes directly into freshly allocated blocks; decode gathers K/V through
  the block table inside the vmapped step and appends to the tail block
  inside the fused chunk. With ``share_prefix=True`` (default) a
  :class:`~repro.serve.batch.PrefixIndex` aliases common prompt prefixes to
  the blocks that already hold them — prefix K/V is written once, admission
  counts resident shared blocks as zero additional need, an exact
  whole-prompt hit (resubmission, preemption restart) skips prefill compute
  entirely, and a shared tail block is copy-on-write forked before any
  slot's fused append writes to it. Streams stay bitwise identical to the
  ``share_prefix=False`` drain and to serial decode
  (tests/test_prefix_sharing.py, tests/test_cow_properties.py).

* ``mode="continuous"`` (default) — a
  :class:`~repro.serve.scheduler.SlotScheduler` owns ``max_batch`` decode
  slots with dense worst-case ``capacity`` reservations; each queued request
  is prefilled *individually* (exact prompt length, batch 1) and its cache
  written into a free slot mid-decode
  (:func:`repro.serve.batch.write_slot`). Decode runs ``decode_chunk``
  tokens per device dispatch (:func:`repro.serve.steps.make_fused_decode`)
  with in-scan EOS/budget masking.

* ``mode="cohort"`` — the legacy fixed-cohort drain (left-padded batch
  prefill, one jit call per token), kept as the baseline that
  ``benchmarks/serve_bench.py`` measures the other modes against.

``run()`` drains the queue to ``{rid: tokens}``; ``stream()`` is a generator
yielding ``(rid, delta_tokens, done)`` per-request deltas at admission and at
every chunk boundary (paged + continuous modes). Per-request streams are
bitwise identical to serial one-request-at-a-time greedy decode in both
modes (tests/test_scheduler.py, tests/test_paged.py).

Single-process greedy sampling; the dry-run proves the sharded lowering.
"""
from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.paged_attention import grid_steps
from repro.models.config import ModelConfig
from repro.serve.batch import (BlockPool, PrefixIndex, copy_block,
                               init_slot_cache, slot_axes, write_prefill,
                               write_slot)
from repro.serve.scheduler import Request, SlotScheduler
from repro.serve.spec import SpecConfig, make_spec_decode
from repro.serve.steps import (make_decode_step, make_fused_decode,
                               make_paged_decode, make_paged_kernel_decode,
                               make_prefill_step)

PAGED_FAMILIES = ("dense", "vlm", "moe")
KV_IMPLS = ("auto", "kernel", "pallas", "reference")


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params, *, capacity: int = 256,
                 max_batch: int = 8, eos_id: int | None = None,
                 mode: str = "continuous", decode_chunk: int = 8,
                 prefill_bucket: bool = False, block_size: int = 16,
                 num_blocks: int | None = None, kv_impl: str = "auto",
                 share_prefix: bool = True,
                 speculate: SpecConfig | None = None, recorder=None):
        if mode not in ("continuous", "cohort", "paged"):
            raise ValueError(
                f"mode must be continuous|cohort|paged, got {mode!r}")
        if speculate is not None and mode != "paged":
            raise ValueError("speculate=SpecConfig(...) requires mode='paged'")
        if recorder is None:
            from repro.obs.recorder import NullRecorder
            recorder = NullRecorder()
        # Host-side only: the recorder sees counters/spans at the chunk
        # boundaries the loop already crosses and never touches the device
        # computation, so streams are bitwise identical with obs on or off
        # (pinned in tests/test_serve_obs.py).
        self.recorder = recorder
        self.cfg, self.params = cfg, params
        self.capacity, self.max_batch = capacity, max_batch
        self.eos_id, self.mode, self.decode_chunk = eos_id, mode, decode_chunk
        self.block_size = block_size
        # pad admission prefills to power-of-two lengths so a mixed-length
        # workload compiles O(log S) prefill programs instead of one per
        # distinct prompt length. Right-padding is causally masked, so it is
        # numerically exact up to gemm reduction order (~1e-6 on the last
        # logits; NOT bitwise — the bitwise serial-equivalence contract is
        # tested with exact-length prefill). Recurrent state (ssm/hybrid) and
        # ring caches (sliding window) absorb pad tokens, and MoE expert
        # capacity C ∝ token count means padding changes which valid tokens
        # routing drops — so bucketing only ever applies to dense-MLP
        # full-attention families.
        self._bucket = (prefill_bucket and cfg.window is None
                        and cfg.family in ("dense", "vlm", "audio"))
        self.scheduler = SlotScheduler(max_batch)
        self._prefill = jax.jit(make_prefill_step(cfg, capacity))
        self._decode = jax.jit(make_decode_step(cfg))
        # donation is a no-op (and warns) on CPU
        donate = jax.default_backend() != "cpu"
        self.pool: BlockPool | None = None
        self.kv_impl: str | None = None  # resolved policy (paged mode only)
        self.prefix: PrefixIndex | None = None  # set in paged mode
        if mode == "continuous":
            axes = slot_axes(cfg, capacity, params=params)
            self._fused_decode = jax.jit(
                make_fused_decode(cfg, axes, decode_chunk, eos_id),
                donate_argnums=(1, 2, 3, 4) if donate else ())
            self._write_slot = jax.jit(partial(write_slot, axes=axes),
                                       donate_argnums=(0,) if donate else ())
        elif mode == "paged":
            if cfg.family not in PAGED_FAMILIES or cfg.window is not None:
                raise ValueError(
                    "paged mode needs a full-attention KV-cache family "
                    f"(one of {PAGED_FAMILIES}, window=None); got "
                    f"family={cfg.family!r} window={cfg.window!r}")
            if num_blocks is None:
                # parity default: the same KV HBM a continuous engine of this
                # max_batch/capacity would reserve up front
                num_blocks = max_batch * capacity // block_size
            self.pool = BlockPool(cfg, num_blocks=num_blocks,
                                  block_size=block_size, max_batch=max_batch,
                                  capacity=capacity, params=params)
            # KV read-path policy. "reference": the PR-5 per-slot
            # gather/scatter path (models.decode_step, bitwise the serial
            # computation). "kernel": the block-native path — Pallas
            # paged-attention compiled on TPU, its jnp-gather oracle
            # elsewhere. "pallas": the kernel forced in interpret mode
            # (CPU CI parity). "auto" resolves by backend: kernel on TPU,
            # reference on CPU — preserving the bitwise serial-equivalence
            # contract wherever the compiled kernel can't run.
            if kv_impl not in KV_IMPLS:
                raise ValueError(
                    f"kv_impl must be one of {KV_IMPLS}, got {kv_impl!r}")
            if kv_impl == "auto":
                from repro.kernels import on_tpu
                kv_impl = "kernel" if on_tpu() else "reference"
            self.kv_impl = kv_impl
            if kv_impl == "reference":
                step_fn = make_paged_decode(
                    cfg, self.pool.batch_axes, self.pool.cap_axes,
                    block_size, decode_chunk, eos_id)
            else:
                # "pallas" forces the kernel; interpret=None lets the
                # use_pallas policy pick compiled-on-TPU / interpret-on-CPU
                step_fn = make_paged_kernel_decode(
                    cfg, block_size, decode_chunk, eos_id,
                    impl="pallas" if kv_impl == "pallas" else "auto")
            self._paged_decode = jax.jit(
                step_fn, donate_argnums=(1, 2, 4, 5, 6) if donate else ())
            self._write_prefill = jax.jit(
                partial(write_prefill, batch_axes=self.pool.batch_axes,
                        cap_axes=self.pool.cap_axes, block_size=block_size),
                donate_argnums=(0,) if donate else ())
            # shared-prefix copy-on-write: a content-hash index over resident
            # block runs (admission attaches instead of re-writing) plus the
            # jitted device-side page copy that mirrors fork_for_write.
            self.prefix = PrefixIndex(self.pool.alloc) if share_prefix \
                else None
            self._copy_block = jax.jit(
                copy_block, donate_argnums=(0,) if donate else ())
            if speculate is not None:
                dcfg = speculate.draft_cfg
                if dcfg.vocab != cfg.vocab:
                    raise ValueError(
                        "draft vocab must match the target's: "
                        f"{dcfg.vocab} != {cfg.vocab}")
                # every speculative round rewinds the draft by overwriting
                # its cache ``idx`` — only sound when ALL decode-time state
                # is position-indexed KV (plus static cross-attn): recurrent
                # state folds rejected drafts in irreversibly, and a window
                # ring cache may already have evicted the rewind target
                if dcfg.window is not None or dcfg.family not in (
                        "dense", "vlm", "moe", "audio"):
                    raise ValueError(
                        "speculative draft needs a full-attention KV family "
                        "(rewind = idx overwrite); got "
                        f"family={dcfg.family!r} window={dcfg.window!r}")
                # the draft runs the plain dense slot-decode path against its
                # own worst-case-reserved cache — no block accounting, its
                # state is disposable (rebuilt from the true stream at every
                # round's rewind)
                self._draft_axes = slot_axes(dcfg, capacity,
                                             params=speculate.draft_params)
                self._draft_prefill = jax.jit(make_prefill_step(dcfg,
                                                                capacity))
                self._write_draft = jax.jit(
                    partial(write_slot, axes=self._draft_axes),
                    donate_argnums=(0,) if donate else ())
                self._spec_rounds = speculate.rounds_for(decode_chunk)
                spec_fn = make_spec_decode(
                    cfg, dcfg, self._draft_axes, block_size, speculate.k,
                    self._spec_rounds, eos_id,
                    impl=kv_impl if kv_impl in ("reference", "pallas")
                    else "auto")
                # tables (arg 4) stay host-owned, like the single-token path
                self._spec_decode = jax.jit(
                    spec_fn,
                    donate_argnums=(2, 3, 5, 6, 7, 8) if donate else ())
        self.spec = speculate
        self._dcache = None  # draft slot cache, created per drain
        self._next_rid = 0
        self._streamed: dict[int, int] = {}
        self.stats: dict = {}
        self.completed: dict[int, Request] = {}

    # -- request intake ------------------------------------------------------

    def submit(self, prompt, max_new_tokens: int = 16) -> int:
        prompt = np.asarray(prompt, np.int32)
        if self.mode == "paged":
            total = len(prompt) + max_new_tokens
            if (total > self.capacity
                    or self.pool.blocks_for(total) > self.pool.num_blocks):
                self.recorder.counter_add("serve_submit_rejects")
                raise ValueError(
                    f"request needs {total} cache positions "
                    f"({self.pool.blocks_for(total)} blocks); pool holds "
                    f"{self.pool.num_blocks} blocks of {self.block_size} "
                    f"with per-request capacity {self.capacity}")
        rid = self._next_rid
        self._next_rid += 1
        req = Request(rid, prompt, max_new_tokens,
                      submit_s=time.perf_counter())
        self.scheduler.submit(req)
        self.recorder.counter_add("serve_submitted")
        self.recorder.instant("serve.submit", rid=rid, prompt_len=len(prompt),
                              budget=max_new_tokens)
        return rid

    def _record_done(self, req: Request) -> None:
        """Per-request latency accounting at completion: queue wait, TTFT,
        end-to-end latency, mean inter-token gap, and a ``request_done``
        event keyed by rid (what tests/test_serve_obs.py asserts against)."""
        rec = self.recorder
        if not rec.enabled:
            return
        ttft = (req.first_token_s - req.submit_s) if req.first_token_s else 0.0
        latency = (req.finish_s - req.submit_s) if req.finish_s else 0.0
        rec.counter_add("serve_finished")
        rec.observe("serve_queue_s", req.admit_s - req.submit_s)
        rec.observe("serve_ttft_s", ttft)
        rec.observe("serve_latency_s", latency)
        if req.first_token_s and req.finish_s and len(req.output) > 1:
            rec.observe("serve_itl_s", (req.finish_s - req.first_token_s)
                        / (len(req.output) - 1))
        rec.event("request_done", rid=req.rid, ttft_s=ttft,
                  latency_s=latency, tokens=len(req.output))

    # -- shared helpers ------------------------------------------------------

    def _prefill_inputs(self, tokens: jnp.ndarray,
                        valid_len: int | None = None,
                        cfg: ModelConfig | None = None) -> dict:
        """Family extras (zero-stub modalities) for a [B, S] token batch.

        valid_len: true prompt length when ``tokens`` is right-padded to a
        bucket, so modality extras never land on pad positions. cfg: the
        model the batch feeds (defaults to the target; the speculative draft
        passes its own config)."""
        cfg = cfg or self.cfg
        B, S = tokens.shape
        batch = {"tokens": tokens}
        if cfg.family == "audio":
            batch["src_embeds"] = jnp.zeros(
                (B, cfg.src_len, cfg.d_model), cfg.dtype)
        if cfg.family == "vlm":
            n = min(cfg.n_img_tokens, valid_len or S)
            batch["image_embeds"] = jnp.zeros(
                (B, n, cfg.d_model), cfg.dtype)
            batch["image_pos"] = jnp.tile(
                jnp.arange(n, dtype=jnp.int32)[None], (B, 1))
        return batch

    def _admission_batch(self, req: Request,
                         cfg: ModelConfig | None = None) -> dict:
        """Prefill inputs for one admitted request: exact-length, or padded
        to a power-of-two bucket when ``prefill_bucket`` is on.

        The buckets are shared across every prefill consumer — both serving
        loops (dense continuous and paged) and, under speculation, the
        draft's admission prefill — so target + draft cost one O(log S)
        family of programs each, not one per distinct prompt length.
        Bucket eligibility is re-checked against ``cfg``: a pad-sensitive
        draft (window/ssm/moe) gets exact-length prefill even when the
        target buckets."""
        cfg = cfg or self.cfg
        L = len(req.prompt)
        toks = req.prompt
        length = None
        if self._bucket and cfg.window is None and cfg.family in (
                "dense", "vlm", "audio"):
            pad_to = min(max(8, 1 << max(L - 1, 1).bit_length()),
                         self.capacity)
            if L < pad_to:
                toks = np.zeros(pad_to, np.int32)
                toks[:L] = req.prompt
                length = L
        batch = self._prefill_inputs(jnp.asarray(toks[None]), valid_len=L,
                                     cfg=cfg)
        if length is not None:
            batch["length"] = jnp.asarray(length, jnp.int32)
        return batch

    def _deltas(self, reqs) -> list:
        """The not-yet-streamed suffix of each request's output, as
        ``(rid, delta, done)`` events.

        After a preemption the request regenerates its (bitwise identical)
        tokens from scratch; the per-rid high-water mark suppresses re-yields
        until the regeneration passes what was already streamed."""
        out = []
        for req in reqs:
            delta = req.output[self._streamed.get(req.rid, 0):]
            if delta:
                out.append((req.rid, list(delta), req.done))
        return out

    def _emit(self, events):
        """Yield ``events``, each marked streamed as it is handed over, so a
        consumer that abandons the stream loses none. The loops build events
        inside their spans and yield them outside: no span stays open while
        the consumer runs."""
        for rid, delta, done in events:
            self._streamed[rid] = self._streamed.get(rid, 0) + len(delta)
            yield rid, delta, done

    def _prefill_first_token(self, req: Request):
        """Run the admission prefill; returns (first_token, request cache)."""
        with self.recorder.span("serve.prefill", rid=req.rid,
                                prompt_len=len(req.prompt)):
            logits, req_cache = self._prefill(self.params,
                                              self._admission_batch(req))
            first = int(jnp.argmax(logits[0, -1]))
        if not req.first_token_s:
            req.first_token_s = time.perf_counter()
        return first, req_cache

    # -- continuous batching -------------------------------------------------

    def _stream_continuous(self):
        sched, eos = self.scheduler, self.eos_id
        B = self.max_batch
        src = None
        if self.cfg.family == "audio":
            src = jnp.zeros((B, self.cfg.src_len, self.cfg.d_model),
                            self.cfg.dtype)
        cache = init_slot_cache(self.cfg, B, self.capacity,
                                params=self.params, src_embeds=src)
        tok = np.zeros((B,), np.int32)
        live = np.zeros((B,), bool)
        remaining = np.zeros((B,), np.int32)
        stats = {"prefills": 0, "decode_dispatches": 0, "decode_steps": 0,
                 "live_slot_steps": 0, "emitted_tokens": 0,
                 "peak_concurrency": 0}

        def finish(i: int) -> Request:
            req = sched.release(i)
            req.finish_s = time.perf_counter()
            live[i] = False
            remaining[i] = 0
            self.completed[req.rid] = req
            self._record_done(req)
            return req

        t0 = time.perf_counter()
        try:
            yield from self._continuous_loop(sched, cache, tok, live,
                                             remaining, stats, finish)
        finally:
            self.stats = stats
            self._export_stats(stats, time.perf_counter() - t0)
            self._evict_in_flight()

    def _continuous_loop(self, sched, cache, tok, live, remaining, stats,
                         finish):
        eos, rec, chunk = self.eos_id, self.recorder, self.decode_chunk
        while sched.has_work():
            # admission: prefill queued requests into free slots, mid-decode
            for i, req in sched.admit():
                with rec.span("serve.admit", rid=req.rid):
                    if not req.admit_s:
                        req.admit_s = time.perf_counter()
                    first, req_cache = self._prefill_first_token(req)
                    stats["prefills"] += 1
                    stats["emitted_tokens"] += 1  # the prefill's token
                    if req.add_token(first, eos):
                        finish(i)   # prefill token was EOS or budget == 1
                    else:
                        cache = self._write_slot(cache, req_cache,
                                                 jnp.asarray(i, jnp.int32))
                        tok[i], live[i] = first, True
                        remaining[i] = req.remaining
                    out = self._deltas([req])
                yield from self._emit(out)
            stats["peak_concurrency"] = max(stats["peak_concurrency"],
                                            len(sched.occupied()))
            if not live.any():
                continue  # queue may still hold work; otherwise loop exits
            with rec.span("serve.decode_dispatch", steps=chunk):
                stats["live_slot_steps"] += int(live.sum()) * chunk
                out = self._fused_decode(
                    self.params, jnp.asarray(tok), cache,
                    jnp.asarray(live), jnp.asarray(remaining))
            with rec.span("serve.decode_wait"):
                tok_d, cache, live_d, remaining_d, tokens, emitted = out
                # in place: finish() closes over these same arrays
                tok[:], live[:] = np.asarray(tok_d), np.asarray(live_d)
                remaining[:] = np.asarray(remaining_d)
                tokens, emitted = np.asarray(tokens), np.asarray(emitted)
            stats["decode_dispatches"] += 1
            stats["decode_steps"] += chunk
            stats["emitted_tokens"] += int(emitted.sum())
            with rec.span("serve.emit"):
                reqs = [r for _, r in sched.occupied()]
                for i in sched.record_decode(tokens, emitted, eos):
                    finish(i)
                out = self._deltas(reqs)
            yield from self._emit(out)

    def _export_stats(self, stats: dict, elapsed_s: float) -> None:
        """Add the drain's stats into the recorder (``serve_`` prefix):
        counts as counters, so they sum over drains, and ``peak_*`` as
        gauges; plus the realized tokens/sec for the whole drain."""
        rec = self.recorder
        if not rec.enabled:
            return
        for k, v in stats.items():
            if k.startswith("peak_"):
                rec.gauge_set(f"serve_{k}", v)
            else:
                rec.counter_add(f"serve_{k}", v)
        tps = stats.get("emitted_tokens", 0) / max(elapsed_s, 1e-9)
        rec.gauge_set("serve_tokens_per_sec", tps)
        rec.event("drain_done", elapsed_s=elapsed_s, tokens_per_sec=tps,
                  **stats)
        rec.flush()

    def _evict_in_flight(self) -> None:
        """Return in-flight requests to the queue front (youngest first, so
        FIFO order is preserved). A consumer that abandons ``stream()``
        mid-drain must not strand occupied slots — or, in paged mode, leak
        their KV blocks: the next ``run()``/``stream()`` call re-admits the
        evicted requests and (greedy decode being deterministic) continues
        their streams exactly where the abandoned consumer stopped."""
        sched = self.scheduler
        for i, _ in sorted(sched.occupied(), key=lambda t: -t[1].admit_seq):
            if self.pool is not None:
                self.pool.release(i)
            sched.preempt(i)

    # -- paged KV ------------------------------------------------------------

    def _stream_paged(self):
        sched, pool, eos = self.scheduler, self.pool, self.eos_id
        B, chunk = self.max_batch, self.decode_chunk
        tok = np.zeros((B,), np.int32)
        idx = np.zeros((B,), np.int32)
        live = np.zeros((B,), bool)
        remaining = np.zeros((B,), np.int32)
        stats = {"prefills": 0, "decode_dispatches": 0, "decode_steps": 0,
                 "live_slot_steps": 0, "emitted_tokens": 0, "preemptions": 0,
                 "peak_concurrency": 0,
                 "prefix_hits": 0, "cow_forks": 0, "prefill_tokens": 0,
                 "prefill_s": 0.0, "peak_blocks_in_use": 0,
                 "peak_shared_blocks": 0}
        if self._counts_grid:
            stats.update(paged_grid_steps=0, paged_live_grid_steps=0,
                         paged_live_pages=0)
        if self.spec is not None:
            stats.update(spec_proposed=0, spec_accepted=0, draft_prefills=0)
            src = None
            if self.spec.draft_cfg.family == "audio":
                src = jnp.zeros((B, self.spec.draft_cfg.src_len,
                                 self.spec.draft_cfg.d_model),
                                self.spec.draft_cfg.dtype)
            self._dcache = init_slot_cache(self.spec.draft_cfg, B,
                                           self.capacity,
                                           params=self.spec.draft_params,
                                           src_embeds=src)

        def finish(i: int) -> Request:
            req = sched.release(i)
            pool.release(i)
            req.finish_s = time.perf_counter()
            live[i] = False
            remaining[i] = 0
            self.completed[req.rid] = req
            self._record_done(req)
            return req

        def preempt(i: int) -> None:
            pool.release(i)
            req = sched.preempt(i)
            live[i] = False
            remaining[i] = 0
            stats["preemptions"] += 1
            self.recorder.instant("serve.preempt", rid=req.rid)

        t0 = time.perf_counter()
        try:
            yield from self._paged_loop(tok, idx, live, remaining, stats,
                                        finish, preempt)
        finally:
            self.stats = stats
            self._export_stats(stats, time.perf_counter() - t0)
            self._evict_in_flight()

    def _admission_need(self, req: Request) -> int:
        """Free-list headroom admitting ``req`` costs right now: fresh pages
        its prompt (+1 token) needs beyond the cached-prefix match, plus one
        free-list pop per matched block that must be *revived* (refcount 0 —
        resident shared blocks cost zero additional need), plus one block of
        copy-on-write headroom when an exact match shares a partial tail
        block (the first decode append forks it)."""
        pool = self.pool
        pages = pool.blocks_for(len(req.prompt) + 1)
        m = self.prefix.match(req.prompt) if self.prefix is not None else None
        if m is None:
            return pages
        resident = sum(1 for b in m.blocks if pool.refcount(b) > 0)
        need = pages - resident
        if m.exact and len(req.prompt) % self.block_size:
            need += 1
        return need

    @property
    def _counts_grid(self) -> bool:
        """Whether decode chunks run the block-native paged kernel step."""
        return self.spec is None and self.kv_impl in ("kernel", "pallas")

    def _count_grid(self, stats, idx, emitted, hw: int) -> None:
        """Add the paged kernel's grid over the chunk just run: its step
        ``s`` called the kernel once per layer, on a table ``hw`` pages
        wide, with lengths ``idx + 1 + s`` on the slots it emitted for and
        0 on the rest (``kernels.paged_attention.grid_steps``)."""
        step = np.arange(len(emitted))[:, None]
        lengths = np.where(emitted, idx[None] + 1 + step, 0)
        n_layers = self.pool.data["kv"]["k"].shape[2]
        steps, live_steps, live_pages = grid_steps(lengths, hw,
                                                   self.block_size)
        stats["paged_grid_steps"] += steps * n_layers
        stats["paged_live_grid_steps"] += live_steps * n_layers
        stats["paged_live_pages"] += live_pages * n_layers

    def _paged_loop(self, tok, idx, live, remaining, stats, finish, preempt):
        sched, pool, eos = self.scheduler, self.pool, self.eos_id
        prefix, chunk, rec = self.prefix, self.decode_chunk, self.recorder
        # positions one dispatch can advance a slot: decode_chunk serially,
        # or rounds × (k + 1) verify rows under speculation. Emitted rows
        # only ever read positions below idx + adv, so this is also the
        # pre-chunk ensure horizon; window writes past it trash-route.
        adv = chunk if self.spec is None else (
            self._spec_rounds * (self.spec.k + 1))

        def admit_one(i: int, req: Request) -> None:
            """Prefill (or prefix-hit) one admitted request into slot ``i``
            and write its pages; it may finish at once, or go back to the
            queue front."""
            if not req.admit_s:
                req.admit_s = time.perf_counter()
            # re-match at attach time: an earlier admission this round may
            # have reused a freed-but-cached block the can_admit match
            # counted on (its generation bump invalidates it)
            m = prefix.match(req.prompt) if prefix is not None else None
            if m is not None and m.exact:
                # write-once fast path: every page (incl. the partial tail)
                # and the greedy first token are cached — skip prefill
                # compute entirely and alias the blocks below
                first, req_cache = m.first_tok, None
                stats["prefix_hits"] += 1
                if not req.first_token_s:
                    req.first_token_s = time.perf_counter()
                rec.instant("serve.prefix_hit", rid=req.rid,
                            cached_tokens=m.n_tokens)
            else:
                t_pf = time.perf_counter()
                first, req_cache = self._prefill_first_token(req)
                stats["prefills"] += 1
                stats["prefill_s"] += time.perf_counter() - t_pf
                stats["prefill_tokens"] += len(req.prompt)
            stats["emitted_tokens"] += 1
            if req.add_token(first, eos):
                finish(i)   # prefill token was EOS or budget == 1
                return
            if m is not None:
                pool.attach(i, m.blocks)
            if not pool.ensure(i, len(req.prompt)):
                # the can_admit claim was computed against a larger match
                # than survived to attach time (same-round block reuse) —
                # hand the request back to the queue front instead of
                # oversubscribing; it re-admits next round
                preempt(i)
                return
            if m is None or not m.exact:
                # write once: matched pages stay untouched (their bits are
                # already this prompt's prefix K/V, and the writer may share
                # them with live readers) — route them to trash and scatter
                # only the unmatched tail pages
                tbl = pool.tables[i].copy()
                if m is not None:
                    tbl[:len(m.blocks)] = pool.trash
                pool.data = self._write_prefill(
                    pool.data, req_cache, jnp.asarray(tbl))
                if prefix is not None:
                    prefix.record(req.prompt,
                                  pool.tables[i, :pool.owned(i)], first)
            tok[i], idx[i] = first, len(req.prompt)
            live[i], remaining[i] = True, req.remaining
            if self.spec is not None:
                # draft prefill runs even on exact prefix hits — the draft's
                # dense cache has no prefix index to alias from, and a
                # preemption restart rebuilds it the same way
                with rec.span("serve.draft_prefill", rid=req.rid,
                              prompt_len=len(req.prompt)):
                    _, d_cache = self._draft_prefill(
                        self.spec.draft_params,
                        self._admission_batch(req, cfg=self.spec.draft_cfg))
                    self._dcache = self._write_draft(
                        self._dcache, d_cache, jnp.asarray(i, jnp.int32))
                stats["draft_prefills"] += 1

        while sched.has_work():
            # admission gated on free blocks, not free slots: a request is
            # admitted iff its prompt (+1 headroom) fits the pool right now,
            # where already-resident shared prefix blocks count as zero
            # additional need. ``claimed`` front-runs the attach/ensure calls
            # in admit_one so one round admitting several requests cannot
            # oversubscribe the free list (can_admit only mutates it when it
            # returns True, i.e. exactly when the head IS admitted).
            claimed = [0]

            def can_admit(r) -> bool:
                need = self._admission_need(r)
                if claimed[0] + need > pool.free_blocks:
                    # deterministic given the workload: admission is pure
                    # host-side scheduling, so this counter is identical
                    # whether obs is on or off
                    rec.counter_add("serve_admission_rejects")
                    return False
                claimed[0] += need
                return True

            with rec.span("serve.admit"):
                admitted = sched.admit(can_admit)
            for i, req in admitted:
                with rec.span("serve.admit", rid=req.rid):
                    admit_one(i, req)
                    out = self._deltas([req])
                yield from self._emit(out)
            stats["peak_concurrency"] = max(stats["peak_concurrency"],
                                            len(sched.occupied()))
            stats["peak_blocks_in_use"] = max(
                stats["peak_blocks_in_use"],
                pool.num_blocks - pool.free_blocks)
            if not live.any():
                continue
            # pre-chunk block budget (oldest first): every live slot must
            # make its tail page exclusive (copy-on-write fork — shared
            # blocks are read-only, and the fused append writes through
            # tables[i, idx // block_size]) and cover its chunk's writes
            # before the device program launches. If the pool runs dry,
            # evict the youngest request — it has the least work to redo
            # and re-queues at the front, keeping FIFO.
            with rec.span("serve.block_budget"):
                for i, req in sorted(sched.occupied(),
                                     key=lambda t: t[1].admit_seq):
                    if not live[i]:
                        continue
                    page = int(idx[i]) // self.block_size
                    while pool.needs_fork(i, page):
                        if pool.free_blocks:
                            old, new = pool.fork_for_write(i, page)
                            pool.data = self._copy_block(
                                pool.data, jnp.asarray(old, jnp.int32),
                                jnp.asarray(new, jnp.int32))
                            stats["cow_forks"] += 1
                            break
                        preempt(sched.youngest())  # may drop the shared ref
                    if not live[i]:
                        continue   # preempted itself while hunting fork room
                    need = int(idx[i]) + min(adv, int(remaining[i]))
                    while not pool.ensure(i, need):
                        victim = sched.youngest()
                        if victim == i and len(sched.occupied()) == 1:
                            # unreachable: submit() caps a lone request's
                            # total need at the pool size
                            raise RuntimeError("block pool exhausted by a "
                                               "single request")
                        preempt(victim)
                        if victim == i:
                            break
                stats["peak_shared_blocks"] = max(
                    stats["peak_shared_blocks"], int((pool._refs > 1).sum()))
            if not live.any():
                continue
            # length-clamp: hand the device only the first `hw` table columns
            # (every live slot's blocks sit below the allocator's high-water
            # mark), so reference gathers / kernel grids stop at pages someone
            # has actually reached. Bucketed to the next power of two so jit
            # re-specializes O(log max_blocks) times, not once per width.
            hw = min(1 << max(pool.high_water() - 1, 0).bit_length(),
                     pool.max_blocks)
            with rec.span("serve.decode_dispatch", steps=adv):
                stats["live_slot_steps"] += int(live.sum()) * adv
                if self.spec is None:
                    out = self._paged_decode(
                        self.params, jnp.asarray(tok), pool.data,
                        jnp.asarray(pool.tables[:, :hw]), jnp.asarray(idx),
                        jnp.asarray(live), jnp.asarray(remaining))
                else:
                    out = self._spec_decode(
                        self.params, self.spec.draft_params,
                        jnp.asarray(tok), pool.data,
                        jnp.asarray(pool.tables[:, :hw]), jnp.asarray(idx),
                        jnp.asarray(live), jnp.asarray(remaining),
                        self._dcache)
            with rec.span("serve.decode_wait"):
                if self.spec is None:
                    (tok_d, pool.data, idx_d, live_d, remaining_d, tokens,
                     emitted) = out
                else:
                    (tok_d, pool.data, idx_d, live_d, remaining_d, tokens,
                     emitted, self._dcache, proposed, accepted) = out
                    stats["spec_proposed"] += int(np.asarray(proposed).sum())
                    stats["spec_accepted"] += int(np.asarray(accepted).sum())
                tokens, emitted = np.asarray(tokens), np.asarray(emitted)
                if self._counts_grid:
                    self._count_grid(stats, idx, emitted, hw)
                # in place: finish()/preempt() close over these same arrays
                tok[:], idx[:] = np.asarray(tok_d), np.asarray(idx_d)
                live[:] = np.asarray(live_d)
                remaining[:] = np.asarray(remaining_d)
            stats["decode_dispatches"] += 1
            stats["decode_steps"] += adv
            stats["emitted_tokens"] += int(emitted.sum())
            with rec.span("serve.emit"):
                reqs = [r for _, r in sched.occupied()]
                for i in sched.record_decode(tokens, emitted, eos):
                    finish(i)
                if self.spec is not None:
                    # speculative rewind: return the worst-case ensure
                    # headroom the verify didn't fill (rejected-window tail
                    # blocks) so a partial acceptance never strands pool
                    # pages across chunks
                    for i, _ in sched.occupied():
                        pool.trim(i, int(idx[i]))
                out = self._deltas(reqs)
            yield from self._emit(out)

    # -- cohort drain (legacy baseline) --------------------------------------

    def _pad_batch(self, reqs: list[Request]):
        S = max(len(r.prompt) for r in reqs)
        toks = np.zeros((len(reqs), S), np.int32)
        for i, r in enumerate(reqs):
            toks[i, S - len(r.prompt):] = r.prompt  # left-pad
        return jnp.asarray(toks)

    def _run_cohort(self) -> dict[int, list[int]]:
        results: dict[int, list[int]] = {}
        sched = self.scheduler
        stats = {"prefills": 0, "decode_dispatches": 0, "decode_steps": 0,
                 "emitted_tokens": 0, "peak_concurrency": 0}
        t0 = time.perf_counter()
        while sched.queue:
            reqs = [sched.queue.popleft()
                    for _ in range(min(self.max_batch, len(sched.queue)))]
            sched.n_admitted += len(reqs)  # cohorts bypass the slot table
            now = time.perf_counter()
            for r in reqs:
                r.admit_s = now
            stats["peak_concurrency"] = max(stats["peak_concurrency"],
                                            len(reqs))
            batch = self._prefill_inputs(self._pad_batch(reqs))
            logits, cache = self._prefill(self.params, batch)
            stats["prefills"] += 1
            tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
            now = time.perf_counter()
            for r, t in zip(reqs, np.asarray(tok[:, 0])):
                r.first_token_s = now
                r.add_token(t, self.eos_id)
                stats["emitted_tokens"] += 1
            steps = max(r.max_new_tokens for r in reqs) - 1
            for _ in range(max(steps, 0)):
                if all(r.done for r in reqs):
                    break  # every request finished — stop burning decode steps
                tok, _, cache = self._decode(self.params, tok, cache)
                stats["decode_dispatches"] += 1
                stats["decode_steps"] += 1
                for i, r in enumerate(reqs):
                    if not r.done:
                        r.add_token(int(np.asarray(tok)[i, 0]), self.eos_id)
                        stats["emitted_tokens"] += 1
            now = time.perf_counter()
            for r in reqs:
                r.finish_s = now
                results[r.rid] = r.output
                self.completed[r.rid] = r
                self._record_done(r)
            sched.n_finished += len(reqs)
        self.stats = stats
        self._export_stats(stats, time.perf_counter() - t0)
        return results

    # -- entry points --------------------------------------------------------

    def stream(self):
        """Generator over ``(rid, delta_tokens, done)`` events.

        Deltas arrive at admission (the prefill-produced first token) and at
        every ``decode_chunk`` boundary; each request's concatenated deltas
        are exactly its ``run()`` output, and ``done=True`` rides on its
        final delta. Preempted requests never re-yield tokens that were
        already streamed. Abandoning the generator mid-drain (close/break)
        evicts the in-flight requests back to the queue — slots and KV
        blocks are reclaimed, and a later ``run()``/``stream()`` call
        resumes exactly where the abandoned stream stopped. The legacy
        cohort drain has no chunk boundaries to stream at — use
        ``mode="continuous"`` or ``mode="paged"``."""
        if self.mode == "cohort":
            raise ValueError("stream() requires mode='continuous'|'paged'")
        gen = (self._stream_paged() if self.mode == "paged"
               else self._stream_continuous())
        yield from gen

    def run(self) -> dict[int, list[int]]:
        """Drain the queue; returns {rid: generated tokens}."""
        if self.mode == "cohort":
            return self._run_cohort()
        results: dict[int, list[int]] = {}
        for rid, delta, _done in self.stream():
            results.setdefault(rid, []).extend(delta)
        return results
