"""What serving needs to know of one architecture, one module per
``model_type`` (the configuration file's own key): ``bench/models/<type>.py``.

A module provides

* ``program_config(cfg)`` — the program's ``ModelConfig``;
* ``weights(cfg, seed)`` — random weights from the seed, made on the device
  in the layout the program serves them in;
* ``reference_logits(params, cfg, prompt, served, *, pad_to, quant=None)`` —
  the plain reference's logits at the positions where ``served`` was
  produced; ``quant="fp8"`` is the serving check's control;

and, each optional (a metric whose count is missing reads nothing):

* ``decode_token_flops(cfg, kv_len)`` — one decode token's model FLOPs;
* ``ATTENTION_KERNEL`` — the op-name part the decode-attention kernel
  carries in the device trace;
* ``attention_cost(cfg, kv_lens)`` — ``(flops, bytes)`` of one kernel call;
* ``attention_calls(cfg)`` — the kernel's calls per decode step.

A configuration is loaded with the directory it came from (``common.
load_config``), and its module is looked up there, so a new architecture is
new files only.
"""
from __future__ import annotations

import pathlib

from bench import common


def of(cfg: dict):
    """The module of ``cfg["model_type"]``; raises, naming the file, where
    there is none."""
    bench_dir = pathlib.Path(cfg.get(common.BENCH_DIR_KEY, common.BENCH))
    path = bench_dir / "models" / f"{cfg['model_type']}.py"
    if not path.is_file():
        raise FileNotFoundError(
            f"configuration {cfg.get('name')!r} has model_type "
            f"{cfg['model_type']!r}, and there is no "
            f"bench/models/{cfg['model_type']}.py ({path})")
    return common.load_module(path)
