import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

"""Multi-pod dry-run: lower + compile every (arch × input-shape × mesh) and
extract memory / cost / collective-roofline numbers — no real allocation
(inputs are ShapeDtypeStructs).

Usage:
  python -m repro.launch.dryrun --arch qwen2.5-3b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod|--both]
  python -m repro.launch.dryrun --arch ... --shape ... --mix ring --tag ringmix

Results land in benchmarks/results/dryrun/<arch>__<shape>__<mesh>[__tag].json.
"""
import argparse
import json
import time
import traceback
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, get, pairs
from repro.launch.hlo_analysis import analyze
from repro.launch.mesh import make_debug_mesh, make_production_mesh
from repro.launch.roofline import (Roofline, collective_bytes, model_flops,
                                   useful_ratio)
from repro.serve.steps import cache_specs, make_decode_step, make_prefill_step
from repro.sharding.hints import hints
from repro.sharding.rules import batch_pspecs, cache_pspecs, param_pspecs
from repro.train.decentral import (TrainerConfig, make_mix, make_step_fns,
                                   node_keys_spec, state_shape,
                                   step_batch_specs)

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "benchmarks", "results", "dryrun")


def _ns(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def _node_axes(spec, mesh):
    names = mesh.axis_names
    if spec.train_mode == "fsdp_gt":
        axes = tuple(a for a in ("pod",) if a in names)
    else:
        axes = tuple(a for a in ("pod", "data") if a in names)
    K = 1
    for a in axes:
        K *= mesh.shape[a]
    return axes, K


def _activation_hints(spec, cfg, mesh, *, serve: bool = False) -> dict:
    """Sharding hints for intermediates SPMD tends to replicate.

    fsdp_gt (and serving on any mesh): activations [B, S, D] batch-sharded
    over data(+pod); MoE dispatch buffers expert-parallel when E divides the
    model axis, token-sharded otherwise (grok: E=8 on a 16-wide axis)."""
    names = mesh.axis_names
    out = {}
    if spec.train_mode == "fsdp_gt" or serve:
        baxes = tuple(a for a in ("pod", "data") if a in names)
        if baxes:
            out["act"] = P(baxes, None, None)
    if cfg.family == "moe":
        msz = mesh.shape.get("model", 1)
        dax = "data" if "data" in names else None
        grouped = getattr(cfg, "moe_groups", 1) > 1
        if cfg.n_experts % msz == 0 and msz > 1:
            out["moe_ecd"] = P("model", dax, None)
            out["moe_ecf"] = P("model", dax, None)
            if grouped:
                out["moe_egcd"] = P("model", dax, None, None)
                out["moe_egcf"] = P("model", dax, None, None)
        else:
            out["moe_ecd"] = P(None, dax, "model")
            out["moe_ecf"] = P(None, dax, "model")
            if grouped:
                out["moe_egcd"] = P(None, dax, None, "model")
                out["moe_egcf"] = P(None, dax, None, "model")
    return out


def _batch_extra_specs(cfg, n: int, seq: int):
    extras = {}
    if cfg.family == "vlm":
        ni = min(cfg.n_img_tokens, seq)
        extras["image_embeds"] = jax.ShapeDtypeStruct((n, ni, cfg.d_model),
                                                      cfg.dtype)
        extras["image_pos"] = jax.ShapeDtypeStruct((n, ni), jnp.int32)
    if cfg.family == "audio":
        extras["src_embeds"] = jax.ShapeDtypeStruct(
            (n, cfg.src_len, cfg.d_model), cfg.dtype)
    return extras


# ---------------------------------------------------------------------------
# Step builders: return (fn, args_shapes, in_shardings, out_shardings)
# ---------------------------------------------------------------------------

def build_train(spec, shape, mesh, tc: TrainerConfig):
    cfg = spec.config
    node_axes, K = _node_axes(spec, mesh)
    per_node = max(shape.global_batch // K, 1)
    fsdp = spec.train_mode == "fsdp_gt"

    problem, _init, step = make_step_fns(cfg, tc)
    mix = make_mix(tc, K)
    fn = partial(step, mix)

    st_sh = state_shape(cfg, tc, K)
    batch_sh = step_batch_specs(cfg, tc, K, per_node, shape.seq_len)
    keys_sh = node_keys_spec(K)

    # node_axes may be empty (fsdp_gt on a single pod: K=1, node dim present
    # but unsharded) — pass the tuple so param_pspecs still strips the dim.
    ax = node_axes if node_axes else None
    x_spec = P(ax, None)
    y_specs = param_pspecs(cfg, st_sh.y, mesh, node_axis=node_axes, fsdp=fsdp)
    st_specs = st_sh._replace(
        x=x_spec, u=x_spec, zf=x_spec,
        y=y_specs, v=y_specs, zg=y_specs,
        **({"x_prev": x_spec, "y_prev": y_specs}
           if hasattr(st_sh, "x_prev") else {}))
    batch_axes = ("data",) if fsdp else ()
    b_specs = batch_pspecs(batch_sh, mesh, node_axis=node_axes,
                           batch_axes=batch_axes)
    k_spec = P(ax) if ax else P(None)
    in_sh = (_ns(mesh, st_specs), _ns(mesh, b_specs),
             NamedSharding(mesh, k_spec))
    out_sh = _ns(mesh, st_specs)
    h = _activation_hints(spec, cfg, mesh)
    return fn, (st_sh, batch_sh, keys_sh), in_sh, out_sh, h


def _serve_param_shardings(spec, cfg, mesh):
    from repro.models import init_params
    p_sh = jax.eval_shape(lambda k: init_params(cfg, k),
                          jax.random.PRNGKey(0))
    fsdp = spec.train_mode == "fsdp_gt"
    specs = param_pspecs(cfg, p_sh, mesh, node_axis=None, fsdp=fsdp)
    return p_sh, _ns(mesh, specs)


def build_prefill(spec, shape, mesh):
    cfg = spec.model_for_shape(shape.name)
    B, S = shape.global_batch, shape.seq_len
    capacity = min(S, cfg.window or S)
    fn = make_prefill_step(cfg, capacity)
    p_sh, p_ns = _serve_param_shardings(spec, cfg, mesh)
    batch_sh = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    batch_sh.update(_batch_extra_specs(cfg, B, S))
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    b_specs = batch_pspecs(batch_sh, mesh, node_axis=None,
                           batch_axes=batch_axes)
    in_sh = (p_ns, _ns(mesh, b_specs))
    h = _activation_hints(spec, cfg, mesh, serve=True)
    return fn, (p_sh, batch_sh), in_sh, None, h


def build_decode(spec, shape, mesh):
    cfg = spec.model_for_shape(shape.name)
    B, S = shape.global_batch, shape.seq_len
    capacity = min(S, cfg.window or S)
    if cfg.family == "hybrid":
        capacity = min(capacity, max(cfg.local_window, 1))
    fn0 = make_decode_step(cfg)

    def fn(params, tokens, cache):
        return fn0(params, tokens, cache)

    p_sh, p_ns = _serve_param_shardings(spec, cfg, mesh)
    tok_sh = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    c_sh = cache_specs(cfg, B, capacity)
    c_sh["idx"] = jax.ShapeDtypeStruct((), jnp.int32)
    c_specs = cache_pspecs(c_sh, mesh, batch=B)
    c_specs["idx"] = P()
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    t_specs = batch_pspecs({"t": tok_sh}, mesh, node_axis=None,
                           batch_axes=batch_axes)["t"]
    in_sh = (p_ns, NamedSharding(mesh, t_specs), _ns(mesh, c_specs))
    h = _activation_hints(spec, cfg, mesh, serve=True)
    h.pop("act", None)  # decode activations are [B,1,D]; leave to SPMD
    return fn, (p_sh, tok_sh, c_sh), in_sh, None, h


# ---------------------------------------------------------------------------
# Run one (arch, shape, mesh)
# ---------------------------------------------------------------------------

def run_pair(arch: str, shape_name: str, *, multi_pod: bool = False,
             mesh=None, tc: TrainerConfig | None = None, tag: str = "",
             out_dir: str | None = None, verbose: bool = True,
             variant: dict | None = None) -> dict:
    """variant: perf-iteration knobs — {embed_fsdp: bool, act_model: bool,
    capacity_factor: float, chunk?}."""
    variant = variant or {}
    from repro.sharding import rules as _rules
    _rules._EMBED_DATA[0] = variant.get("embed_fsdp", True)
    spec = get(arch)
    overrides = {}
    if variant.get("capacity_factor"):
        overrides["capacity_factor"] = float(variant["capacity_factor"])
    if variant.get("moe_groups"):
        overrides["moe_groups"] = int(variant["moe_groups"])
    if overrides:
        import dataclasses as _dc
        spec = _dc.replace(spec,
                           config=spec.config.with_overrides(**overrides))
    shape = SHAPES[shape_name]
    tc = tc or TrainerConfig()
    mesh = mesh if mesh is not None else make_production_mesh(
        multi_pod=multi_pod)
    n_chips = mesh.size
    mesh_name = "x".join(str(mesh.shape[a]) for a in mesh.axis_names)

    t0 = time.time()
    if shape.kind == "train":
        fn, args, in_sh, out_sh, hint = build_train(spec, shape, mesh, tc)
    elif shape.kind == "prefill":
        fn, args, in_sh, out_sh, hint = build_prefill(spec, shape, mesh)
    else:
        fn, args, in_sh, out_sh, hint = build_decode(spec, shape, mesh)

    if variant.get("act_model"):
        if "act" in hint:
            old = hint["act"]
            hint["act"] = P(*(list(old)[:-1] + ["model"]))
        else:  # dp mode: [B, S, D] per node under vmap — shard D
            hint["act"] = P(None, None, "model")
    with mesh, hints(**hint):
        jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh)
        lowered = jitted.lower(*args)
        compiled = lowered.compile()

    compile_s = time.time() - t0
    cost = compiled.cost_analysis()
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)  # per-op-kind, unmultiplied (reference)
    # trip-count-aware analysis: XLA's cost_analysis counts while bodies
    # once, under-reporting scan-over-layers programs by ~n_layers×.
    acc = analyze(hlo)

    rl = Roofline(
        flops_per_device=float(acc["flops"]),
        hbm_bytes_per_device=float(acc["traffic_bytes"]),
        collective_bytes_per_device=float(acc["collective_bytes"]))

    mf = model_flops(spec, shape, n_chips)
    result = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "n_chips": n_chips, "kind": shape.kind,
        "train_mode": spec.train_mode, "tag": tag,
        "compile_s": round(compile_s, 1),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_per_device_gb": round(
                (mem.argument_size_in_bytes + mem.temp_size_in_bytes +
                 mem.output_size_in_bytes - mem.alias_size_in_bytes) / 2**30,
                3),
        },
        "roofline": rl.as_dict(),
        "collectives": {**coll,
                        **{f"counted_{k}": v for k, v in acc.items()
                           if k.endswith("_bytes")}},
        "xla_cost_reference": {"flops": float(cost.get("flops", 0.0)),
                               "bytes": float(cost.get("bytes accessed",
                                                       0.0))},
        "model_flops_global": mf,
        "useful_ratio": round(
            useful_ratio(spec, shape, rl.flops_per_device, n_chips), 4),
    }
    if out_dir is None:
        out_dir = RESULTS_DIR
    os.makedirs(out_dir, exist_ok=True)
    fname = f"{arch}__{shape_name}__{mesh_name}" + (f"__{tag}" if tag else "")
    with open(os.path.join(out_dir, fname + ".json"), "w") as f:
        json.dump(result, f, indent=1)
    if verbose:
        r = result["roofline"]
        print(f"[ok] {arch:22s} {shape_name:12s} mesh={mesh_name:10s} "
              f"compile={compile_s:6.1f}s mem/dev={result['memory']['peak_per_device_gb']:7.2f}GB "
              f"t_comp={r['t_compute_s']:.2e} t_mem={r['t_memory_s']:.2e} "
              f"t_coll={r['t_collective_s']:.2e} dom={r['dominant']}",
              flush=True)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both", action="store_true")
    ap.add_argument("--debug-mesh", action="store_true",
                    help="tiny 2x2 (or 2x2x2) mesh for tests")
    ap.add_argument("--algo", default="mdbo")
    ap.add_argument("--mix", default="dense", choices=["dense", "ring"])
    ap.add_argument("--J", type=int, default=2)
    ap.add_argument("--tag", default="")
    ap.add_argument("--no-embed-fsdp", action="store_true")
    ap.add_argument("--act-model", action="store_true")
    ap.add_argument("--capacity-factor", type=float, default=None)
    ap.add_argument("--moe-groups", type=int, default=None)
    ap.add_argument("--out-dir", default=None)
    args = ap.parse_args()

    tc = TrainerConfig(algo=args.algo, J=args.J, mix=args.mix)

    def mesh_for(mp):
        if args.debug_mesh:
            return make_debug_mesh(multi_pod=mp)
        return make_production_mesh(multi_pod=mp)

    pods = [False, True] if args.both else [args.multi_pod]
    todo = []
    if args.all:
        for (arch, shape_name), skip in pairs(include_skips=True):
            if skip is None:
                todo.append((arch, shape_name))
            else:
                print(f"[skip] {arch} {shape_name}: {skip}")
    else:
        todo.append((args.arch, args.shape))

    failures = []
    for mp in pods:
        mesh = mesh_for(mp)
        for arch, shape_name in todo:
            try:
                run_pair(arch, shape_name, mesh=mesh, tc=tc, tag=args.tag,
                         out_dir=args.out_dir,
                         variant={"embed_fsdp": not args.no_embed_fsdp,
                                  "act_model": args.act_model,
                                  "capacity_factor": args.capacity_factor,
                                  "moe_groups": args.moe_groups})
            except Exception as e:  # noqa: BLE001
                failures.append((arch, shape_name, mp, repr(e)))
                print(f"[FAIL] {arch} {shape_name} multi_pod={mp}: {e}")
                traceback.print_exc()
    if failures:
        raise SystemExit(f"{len(failures)} dry-run failures: {failures}")


if __name__ == "__main__":
    main()
