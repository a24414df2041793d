"""Dense llama-style LMs (``model_type`` "llama": the smollm family): the
program's dense family, the benchmark's dense weights and float32
reference, and the counts of ``bench/flops.py``."""
from __future__ import annotations

from bench import flops
from bench import weights as bench_weights
from bench.reference import dense_lm

# the op-name part of the Pallas paged decode-attention kernel
ATTENTION_KERNEL = "paged_attention"
weights = bench_weights.dense_lm
decode_token_flops = flops.decode_token_flops
attention_cost = flops.paged_attention_cost
reference_logits = dense_lm.next_token_logits


def program_config(cfg: dict):
    """The program's ``ModelConfig`` for a bench configuration file."""
    import jax.numpy as jnp
    from repro.models.config import ModelConfig
    d = flops.lm_dims(cfg)
    return ModelConfig(
        name=cfg["name"], family="dense", n_layers=d["L"], d_model=d["D"],
        n_heads=d["H"], n_kv_heads=d["Hkv"], head_dim=d["Dh"], d_ff=d["F"],
        vocab=d["V"], rope_theta=float(cfg["rope_theta"]),
        dtype=jnp.dtype(cfg["torch_dtype"]),
        param_dtype=jnp.dtype(cfg["param_dtype"]),
        remat=bool(cfg.get("remat", False)))


def attention_calls(cfg: dict) -> int:
    """One kernel call per layer in each decode step."""
    return cfg["num_hidden_layers"]
