"""CLI serving driver (smoke-scale on CPU).

Continuous batching (slot scheduler + scan-fused decode) by default; paged
KV (block-table indirection, full-attention KV families) and the legacy
cohort drain stay available for comparison:

  python -m repro.launch.serve --arch rwkv6-1.6b --reduced --requests 6
  python -m repro.launch.serve --arch qwen2.5-3b --reduced --mode cohort
  python -m repro.launch.serve --arch smollm-360m --reduced --mode paged \
      --block-size 8 --num-blocks 16
  python -m repro.launch.serve --arch smollm-360m --reduced --mode paged \
      --block-size 8 --kv-impl pallas   # force the kernel (interpret on CPU)

Prompts are random tokens, between a quarter and a half of ``--capacity``
long (at least 3), and the weights are random from a fixed seed.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get
from repro.launch.compile_cache import use_compile_cache
from repro.models import init_params
from repro.obs import cli_recorder
from repro.serve import ServeEngine


def main(argv=None):
    """Serve the requests; returns ``(engine, {rid: tokens})`` so an
    in-process caller can inspect what ran."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--capacity", type=int, default=64)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--mode", choices=("continuous", "cohort", "paged"),
                    default="continuous")
    ap.add_argument("--decode-chunk", type=int, default=8,
                    help="decode tokens per fused dispatch")
    ap.add_argument("--block-size", type=int, default=16,
                    help="KV positions per block (paged mode)")
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="physical KV blocks in the pool (paged mode; "
                         "default: max_batch*capacity/block_size)")
    ap.add_argument("--kv-impl", choices=("auto", "kernel", "pallas",
                                          "reference"), default="auto",
                    help="paged attention implementation: block-native "
                         "kernel (Pallas on TPU, jnp block-walk oracle "
                         "elsewhere), forced Pallas (interpret off-TPU), "
                         "or the bitwise gather/scatter reference; auto = "
                         "kernel on TPU, reference elsewhere")
    ap.add_argument("--metrics", default=None, metavar="DIR",
                    help="write metrics.jsonl + metrics.prom into DIR")
    ap.add_argument("--trace-dir", default=None, metavar="DIR",
                    help="write a Perfetto-loadable trace.json into DIR")
    args = ap.parse_args(argv)
    use_compile_cache()

    spec = get(args.arch)
    cfg = spec.reduced() if args.reduced else spec.config
    params = init_params(cfg, jax.random.PRNGKey(0))
    recorder, finalize_obs = cli_recorder(args.metrics, args.trace_dir)
    eng = ServeEngine(cfg, params, capacity=args.capacity,
                      max_batch=args.max_batch, mode=args.mode,
                      decode_chunk=args.decode_chunk,
                      block_size=args.block_size, num_blocks=args.num_blocks,
                      kv_impl=args.kv_impl, recorder=recorder)
    rng = np.random.default_rng(0)
    lo = max(3, args.capacity // 4)
    hi = max(lo, min(args.capacity // 2, args.capacity - args.max_new))
    for i in range(args.requests):
        prompt = rng.integers(0, cfg.vocab, size=rng.integers(lo, hi + 1))
        eng.submit(prompt, max_new_tokens=args.max_new)
    t0 = time.time()
    results = eng.run()
    dt = time.time() - t0
    total_toks = sum(len(v) for v in results.values())
    for rid, toks in sorted(results.items()):
        print(f"req {rid}: {toks}")
    print(f"{total_toks} tokens in {dt:.2f}s "
          f"({total_toks / dt:.1f} tok/s, {args.requests} requests, "
          f"mode={args.mode})")
    if eng.stats:
        print("  " + ", ".join(f"{k}={v}" for k, v in eng.stats.items()))
    for p in finalize_obs():
        print("obs:", p)
    return eng, results


if __name__ == "__main__":
    main()
