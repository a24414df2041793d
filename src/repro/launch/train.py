"""CLI trainer on the Engine substrate: decentralized bilevel (MDBO/VRDBO)
or single-level GT-SGD.

The run loop is :meth:`repro.core.engine.Engine.run` with ``dispatch="fused"``
by default — every ``--eval-every`` interval compiles to ONE scan-fused device
program with the LM batches sampled *inside* the scan
(``data.make_device_lm_sampler``), and the engine's key schedule keeps the
minibatch and per-node J̃ PRNG streams independent. Checkpoints are written at
eval boundaries via ``repro.checkpoint.save``.

When the host has exactly ``--nodes`` devices, each node gets its own device:
the engine runs on a one-axis mesh over them and the gossip is the shard_map
``ring_local`` ppermute. Otherwise every node shares the default device. On
CPU this runs smoke-scale (reduced configs, tiny batches); on a four-chip TPU
host ``--nodes 4`` runs the full configs one node per chip. Examples:

  python -m repro.launch.train --arch smollm-360m --reduced --steps 20
  python -m repro.launch.train --arch rwkv6-1.6b --reduced --algo vrdbo
"""
from __future__ import annotations

import argparse

import jax

from repro.checkpoint import save
from repro.configs import get
from repro.core.common import HParams
from repro.data import make_device_lm_sampler, make_node_batch
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_mesh
from repro.obs import cli_recorder, jax_profile
from repro.train import TrainerConfig, make_trainer_engine, node_axis_name


def main(argv=None):
    """Run the trainer; returns ``{engine, sampler, eval_batch, result,
    state}`` so an in-process caller can check or rerun what it ran."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale model variant (CPU)")
    ap.add_argument("--algo", default="mdbo",
                    choices=["mdbo", "vrdbo", "gt_sgd"])
    ap.add_argument("--mix", default="ring", choices=["ring", "dense"])
    ap.add_argument("--dispatch", default="fused",
                    choices=["fused", "per_step"])
    ap.add_argument("--nodes", type=int, default=4)
    ap.add_argument("--batch", type=int, default=2, help="per-node batch")
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--eval-every", type=int, default=5,
                    help="steps per fused chunk / eval + checkpoint boundary")
    ap.add_argument("--J", type=int, default=2)
    ap.add_argument("--eta", type=float, default=0.1)
    ap.add_argument("--beta1", type=float, default=0.05)
    ap.add_argument("--beta2", type=float, default=0.5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--metrics", default=None, metavar="DIR",
                    help="write metrics.jsonl + metrics.prom into DIR")
    ap.add_argument("--trace-dir", default=None, metavar="DIR",
                    help="write a Perfetto-loadable trace.json into DIR")
    ap.add_argument("--jax-profile", action="store_true",
                    help="additionally capture a jax.profiler device trace "
                         "into --trace-dir")
    args = ap.parse_args(argv)
    use_compile_cache()

    spec = get(args.arch)
    cfg = spec.reduced() if args.reduced else spec.config
    tc = TrainerConfig(algo=args.algo, J=args.J, mix=args.mix,
                       hp=HParams(eta=args.eta, beta1=args.beta1,
                                  beta2=args.beta2))
    K = args.nodes
    recorder, finalize_obs = cli_recorder(args.metrics, args.trace_dir)
    mesh, axis = None, node_axis_name(spec)
    if K > 1 and jax.device_count() == K:
        mesh = make_mesh((K,), (axis,), jax.devices())
    problem, eng = make_trainer_engine(cfg, tc, K, mesh=mesh, axis_name=axis,
                                       dispatch=args.dispatch,
                                       recorder=recorder)
    sampler = make_device_lm_sampler(cfg, tc, K, args.batch, args.seq)
    eval_batch = make_node_batch(cfg, jax.random.PRNGKey(args.seed + 17),
                                 args.batch, args.seq)

    y_sh = jax.eval_shape(problem.init_y, jax.random.PRNGKey(0))
    print(f"arch={cfg.name} algo={args.algo} K={K} dispatch={args.dispatch} "
          f"mix={eng.mix_name} "
          f"params/node={sum(l.size for l in jax.tree.leaves(y_sh)):,}")

    def on_eval(t, state):
        if args.ckpt_dir and t > 0:
            save(args.ckpt_dir, t, {"x": state.x, "y": state.y})

    def run():
        return eng.run(sampler, eval_batch, steps=args.steps, seed=args.seed,
                       eval_every=args.eval_every, on_eval=on_eval,
                       return_state=True)

    if args.jax_profile:
        if not args.trace_dir:
            raise SystemExit("--jax-profile needs --trace-dir")
        with jax_profile(args.trace_dir):
            res, state = run()
    else:
        res, state = run()
    for row in res.as_rows():
        print(f"step {row['step']:4d} val-loss={row['upper_loss']:.4f} "
              f"train-obj={row['lower_loss']:.4f} "
              f"consensus_x={row['consensus_x']:.2e}", flush=True)
    print(f"wall={res.wall_time_s:.1f}s "
          f"({args.steps / max(res.wall_time_s, 1e-9):.2f} steps/s)")
    for p in finalize_obs():
        print("obs:", p)
    if args.ckpt_dir:
        print("checkpoints in", args.ckpt_dir)
    return {"engine": eng, "sampler": sampler, "eval_batch": eval_batch,
            "result": res, "state": state}


if __name__ == "__main__":
    main()
