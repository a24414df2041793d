"""Tiny cells for the CPU tests, written as files into a copy of bench/."""
from __future__ import annotations

import json
import pathlib
import shutil

from bench import common

TINY_LM = {
    "name": "tiny-lm", "num_hidden_layers": 2, "hidden_size": 64,
    "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 512,
    "max_position_embeddings": 128,
}
TINY_SERVE = {
    "config": "tiny-lm", "driver": "serve_open_loop", "chips": 1,
    "engine": {"capacity": 128, "max_batch": 4, "num_blocks": 64},
    "traffic": {"rate_per_s": 6.0,
                "prompt": {"median": 24, "sigma": 0.5, "min": 8, "max": 64},
                "output": {"median": 8, "sigma": 0.5, "min": 2, "max": 16},
                "max_total": 128},
    # on 96 sampled tokens, ten sound tiny runs read 0 to 0.028 on the CPU,
    # the fp8 control 0.35 to 0.78, and an altered token about 4.6
    "check": {"logit_gap": 0.1, "sample_tokens": 96, "pad_to": 128},
}
# a second architecture, under keys of its own (bench/tests/fixtures/tiny_alt.py)
TINY_ALT = {
    "name": "tiny-alt", "model_type": "tiny_alt", "n_layer": 2,
    "d_model": 64, "n_head": 4, "n_kv_head": 1, "d_ff": 96, "vocab": 384,
    "rope_theta": 10000.0,
}
TINY_LOGREG_DATA = {"n": 4000, "d": 20}
TINY_BILEVEL = {
    "config": "tiny-logreg",
    "run": {"nodes": 4, "batch_total": 40, "J": 3, "eval_every": 10},
    # sound tiny runs read <= 3e-8 on the CPU; the bf16 control reads
    # 0.004-0.005 (loss, delta); the faults 0.1-0.8 (delta)
    "check": {"loss_gap": 1e-3, "grad_gap": 1e-3, "delta_gap": 1e-3},
}


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(base[k], v) if isinstance(v, dict) and isinstance(
            base.get(k), dict) else v
    return out


def bench_copy(tmp: pathlib.Path) -> pathlib.Path:
    """A copy of bench/ holding the tiny cells besides the real ones."""
    dst = tmp / "bench"
    shutil.copytree(common.BENCH, dst,
                    ignore=shutil.ignore_patterns("__pycache__", "fixtures"))
    load = lambda p: common.load_json(dst / p)
    write = lambda p, obj: (dst / p).write_text(json.dumps(obj, indent=1))
    write("configs/tiny-lm.json",
          _merge(load("configs/smollm-360m.json"), TINY_LM))
    lr = load("configs/logreg-a9a.json")
    lr["name"] = "tiny-logreg"
    lr["data"] = dict(lr["data"], **TINY_LOGREG_DATA)
    write("configs/tiny-logreg.json", lr)
    write("workloads/tiny.serve.json",
          _merge(load("workloads/smollm-360m.serve-chat.json"), TINY_SERVE))
    write("workloads/tiny.bilevel.json",
          _merge(load("workloads/logreg-a9a.mdbo-k8.json"), TINY_BILEVEL))
    return dst


def add_model(bench: pathlib.Path, model_type: str, cfg: dict,
              module: pathlib.Path | None = None) -> str:
    """Add an architecture as new files only: its module (when given) as
    ``models/<model_type>.py``, the configuration ``cfg``, and a serve
    workload on it that is ``tiny.serve`` in all else. Returns the
    workload's name."""
    if module is not None:
        shutil.copy(module, bench / "models" / f"{model_type}.py")
    (bench / "configs" / f"{cfg['name']}.json").write_text(json.dumps(cfg))
    cell = common.load_json(bench / "workloads" / "tiny.serve.json")
    cell["config"] = cfg["name"]
    name = f"{cfg['name']}.serve"
    (bench / "workloads" / f"{name}.json").write_text(json.dumps(cell))
    return name


def run_tiny(bench_dir, workload: str, seed: int, tmp, *, trace=False,
             seconds=3.0) -> dict:
    from bench import run
    return run.run_cell(workload, seed, seconds, trace, bench_dir=bench_dir,
                        require_chip=False, cache_dir=tmp / "cache",
                        trace_dir=tmp / "trace")
