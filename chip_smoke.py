#!/usr/bin/env python3
"""Smoke run of the system's main paths on a TPU, in one process.

    python chip_smoke.py              # one chip: paged serving + bilevel Engine
    python chip_smoke.py --four-chips # four chips: the decentralized LM trainer

One chip (no arguments):

* serve — ``repro.launch.serve`` at smollm-360m's published widths (32
  layers, d_model 960, 15/5 heads, vocab 49152, bf16; random weights from a
  fixed seed) in paged mode with the Pallas block-table kernel: 12 requests
  with prompts of 256-512 tokens, run to completion. Checks that the engine
  uses the kernel, that the compiled decode chunk holds it
  (``tpu_custom_call``), that the kernel matches ``paged_attention_ref`` on
  the pool the run filled, and that every request got its token budget.
* bilevel — the paper's logistic-regression hyperparameter workload
  (``benchmarks/common.py:build``) through a fused-dispatch ``Engine`` with
  mdbo and with vrdbo. Checks that losses and consensus error are finite and
  fall.

Four chips (``--four-chips``, that phase alone): ``repro.launch.train`` at
full smollm-360m width, mdbo, K=4 nodes, one node per chip (``ring_local``
ppermute gossip), compared with the same seeds through
``Engine(mix="ring_rolled")`` on the same mesh (GSPMD placement).

Exits non-zero when JAX finds no TPU; there is no CPU fallback. The last line
of stdout is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

SERVE_REQUESTS, SERVE_BUDGET = 12, 24
SERVE_ARGV = ["--arch", "smollm-360m", "--mode", "paged", "--kv-impl",
              "kernel", "--capacity", "1024", "--max-batch", "8",
              "--requests", str(SERVE_REQUESTS),
              "--max-new", str(SERVE_BUDGET)]
# one scanned step per chunk: a two-step chunk needs more than one chip's HBM
TRAIN_K, TRAIN_STEPS, TRAIN_EVAL_EVERY, TRAIN_SEED = 4, 3, 1, 0
TRAIN_ARGV = ["--arch", "smollm-360m", "--algo", "mdbo",
              "--nodes", str(TRAIN_K), "--batch", "1", "--seq", "256",
              "--J", "2", "--steps", str(TRAIN_STEPS),
              "--eval-every", str(TRAIN_EVAL_EVERY), "--seed", str(TRAIN_SEED)]
BILEVEL_STEPS, BILEVEL_EVAL_EVERY, BILEVEL_K = 300, 50, 8
# the kernel and the oracle both round their output to bf16 (half an ulp,
# <= 2^-8 of |out| each), and the MXU may take the f32 p.V product in bf16
# passes (<= 2^-9 of max|v|); |out| <= max|v|, so 2^-6 of max|v| bounds it
KERNEL_TOL = 2.0 ** -6
# the two trainer runs differ only in how XLA lays out the same arithmetic;
# a last-bit f32 parameter difference can flip a bf16 rounding in the
# forward pass, which moves a loss by up to ~2^-8 relative
TRAIN_RTOL = 1e-2


def check(ok: bool, what: str) -> None:
    """Fail the run (independently of ``python -O``) when ``ok`` is false."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def require_tpu() -> jax.Device:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform={dev.platform!r}, "
                 f"kind={dev.device_kind!r}); this smoke test runs only on "
                 "the chip")
    return dev


def peak_bytes(dev) -> str:
    stats = dev.memory_stats()
    return f"{stats['peak_bytes_in_use']}/{stats['bytes_limit']}"


def kernel_vs_oracle(eng, seed: int = 0) -> float:
    """Kernel vs ``paged_attention_ref`` on the K/V the serve run wrote.

    Tables point at blocks the run filled (in random order, aliases allowed),
    lengths span empty to full, and slot 0 is dead. Returns the worst error
    as a fraction of max|v|."""
    from repro.kernels.paged_attention import paged_attention
    from repro.kernels.ref import paged_attention_ref

    cfg, pool = eng.cfg, eng.pool
    B, n_pages, bs = eng.max_batch, pool.max_blocks, pool.block_size
    written = eng.stats["peak_blocks_in_use"]
    rng = np.random.default_rng(seed)
    tables = jnp.asarray(rng.integers(0, written, (B, n_pages)), jnp.int32)
    lengths = rng.integers(1, n_pages * bs + 1, B)
    lengths[0] = 0
    lengths = jnp.asarray(lengths, jnp.int32)
    q = jax.random.normal(jax.random.PRNGKey(seed),
                          (B, cfg.n_heads, cfg.head_dim), jnp.bfloat16)
    kp, vp = pool.data["kv"]["k"], pool.data["kv"]["v"]
    worst = 0.0
    for layer in (0, cfg.n_layers // 2, cfg.n_layers - 1):
        out = paged_attention(q, kp, vp, tables, lengths, layer)
        with jax.default_matmul_precision("highest"):
            ref = paged_attention_ref(q, kp, vp, tables, lengths, layer)
        out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
        check(bool(np.isfinite(out).all()), f"layer {layer}: finite output")
        check(not out[0].any(), f"layer {layer}: dead slot emits zeros")
        vmax = float(jnp.max(jnp.abs(vp[:written, :, layer])))
        check(vmax > 0, f"layer {layer}: the run filled the pool")
        worst = max(worst, float(np.max(np.abs(out - ref))) / vmax)
    return worst


def compiled_decode_chunk(eng) -> tuple[str, float]:
    """HLO text of the engine's decode chunk compiled at full table width,
    and the seconds its lowering and compile took."""
    B, pool, i32 = eng.max_batch, eng.pool, jnp.int32
    args = (eng.params, jnp.zeros((B,), i32), pool.data,
            jnp.asarray(pool.tables), jnp.zeros((B,), i32),
            jnp.zeros((B,), bool), jnp.zeros((B,), i32))
    from jax.experimental.compilation_cache import compilation_cache
    # the drain already compiled these shapes: time a lowering and compile
    # that can read neither the in-process caches nor the persistent one
    # (whose on/off state is read once, so it is reset around the toggle)
    jax.clear_caches()
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        t = time.perf_counter()
        hlo = eng._paged_decode.lower(*args).compile().as_text()
        return hlo, time.perf_counter() - t
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def serve_phase(dev) -> None:
    from repro.launch import serve

    t0 = time.perf_counter()
    eng, results = serve.main(SERVE_ARGV)
    wall = time.perf_counter() - t0
    check(eng.kv_impl == "kernel", f"kv_impl={eng.kv_impl}")
    print(f"serve: kv_impl={eng.kv_impl}")

    hlo, compile_s = compiled_decode_chunk(eng)
    check("tpu_custom_call" in hlo, "the decode chunk runs a Pallas kernel")
    print(f"serve: tpu_custom_call in the compiled decode chunk "
          f"({hlo.count('tpu_custom_call')} sites; lowered and compiled in "
          f"{compile_s:.2f} s at {eng.pool.max_blocks} pages)")

    err = kernel_vs_oracle(eng)
    print(f"serve: paged_attention vs paged_attention_ref on the filled pool: "
          f"max|err|/max|v| = {err:.3e} (tolerance {KERNEL_TOL:.3e}: bf16 "
          "output rounding on both sides + bf16 MXU passes, f32 accumulation)")
    check(err <= KERNEL_TOL, f"kernel error {err} <= {KERNEL_TOL}")

    counts = [len(results[r]) for r in sorted(results)]
    check(counts == [SERVE_BUDGET] * SERVE_REQUESTS,
          f"token counts {counts} equal the budget {SERVE_BUDGET}")
    print(f"serve: token counts {counts} == budget {SERVE_BUDGET} for all "
          f"{SERVE_REQUESTS} requests; drain+compile wall {wall:.2f} s; "
          f"peak_bytes_in_use/bytes_limit {peak_bytes(dev)}")


def bilevel_phase() -> None:
    from benchmarks.common import PAPER_HP, build
    from repro.core.engine import Engine

    for algo in ("mdbo", "vrdbo"):
        prob, cfg, sampler, topo = build("a9a-syn", BILEVEL_K)
        eng = Engine(prob, cfg, PAPER_HP[algo], topo, algo=algo,
                     dispatch="fused")
        res = eng.run(sampler, sampler.eval_batch(), steps=BILEVEL_STEPS,
                      eval_every=BILEVEL_EVAL_EVERY)
        up, lo, cy = res.upper_loss, res.lower_loss, res.consensus_y
        print(f"bilevel {algo}: steps {res.steps}")
        print(f"bilevel {algo}: upper {[f'{v:.4f}' for v in up]}")
        print(f"bilevel {algo}: lower {[f'{v:.4f}' for v in lo]}")
        print(f"bilevel {algo}: consensus_y {[f'{v:.3e}' for v in cy]}")
        for name, xs in (("upper", up), ("lower", lo), ("consensus_y", cy)):
            check(all(math.isfinite(v) for v in xs), f"{algo} {name} finite")
        check(up[-1] < up[0] and lo[-1] < lo[0], f"{algo} losses fall")
        check(cy[-1] < cy[0], f"{algo} consensus_y falls")


def four_chip_phase() -> None:
    from repro.core.engine import Engine
    from repro.launch import train

    if jax.device_count() != TRAIN_K:
        sys.exit(f"chip_smoke --four-chips: needs {TRAIN_K} devices, JAX "
                 f"found {jax.device_count()}")
    out = train.main(TRAIN_ARGV)
    eng, res = out["engine"], out["result"]
    check(eng.mix_name == "ring_local" and eng.mesh is not None,
          f"the trainer gossips with ring_local on a mesh ({eng.mix_name})")
    nodes = {}
    for leaf in jax.tree.leaves(out["state"].y):
        check(len(leaf.addressable_shards) == TRAIN_K,
              f"y is split in {TRAIN_K} shards")
        for s in leaf.addressable_shards:
            check(s.data.shape[0] == 1,
                  f"a shard holds one node {s.data.shape}")
            nodes.setdefault(s.device.id, set()).add(s.index[0].start)
    check(len(nodes) == TRAIN_K and all(len(v) == 1 for v in nodes.values())
          and sorted(min(v) for v in nodes.values()) == list(range(TRAIN_K)),
          f"each device holds exactly one node: {nodes}")
    print(f"train: each of {TRAIN_K} devices holds one node's shard of y: "
          + ", ".join(f"device {d} -> node {min(n)}"
                      for d, n in sorted(nodes.items())))
    print("train: ring_local peak_bytes_in_use/bytes_limit per device: "
          + ", ".join(str(peak_bytes(d)) for d in jax.devices()))
    out["state"] = None   # free the ring_local state before the second run

    ref = Engine(eng.problem, eng.cfg, eng.hp, TRAIN_K, algo=eng.algo,
                 mix="ring_rolled", mesh=eng.mesh, axis_name=eng.axis_name)
    res2 = ref.run(out["sampler"], out["eval_batch"], steps=TRAIN_STEPS,
                   seed=TRAIN_SEED, eval_every=TRAIN_EVAL_EVERY)
    print("train: peak_bytes_in_use/bytes_limit per device after the GSPMD "
          "run: " + ", ".join(str(peak_bytes(d)) for d in jax.devices()))
    for name in ("upper_loss", "lower_loss", "consensus_x", "consensus_y"):
        a, b = getattr(res, name), getattr(res2, name)
        print(f"train: {name} ring_local {a} vs ring_rolled {b}")
        check(all(math.isfinite(v) for v in a + b), f"{name} finite")
        np.testing.assert_allclose(a, b, rtol=TRAIN_RTOL, atol=1e-6,
                                   err_msg=name)
    print(f"train: ring_local == GSPMD ring_rolled within rtol {TRAIN_RTOL}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run the four-chip trainer phase, and nothing else")
    args = ap.parse_args(argv)
    dev = require_tpu()
    from repro.launch.compile_cache import use_compile_cache
    print(f"chip_smoke: {dev.device_kind} x{jax.device_count()}, "
          f"jax {jax.__version__}, compile cache {use_compile_cache()}")
    if args.four_chips:
        four_chip_phase()
    else:
        serve_phase(dev)
        bilevel_phase()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
