"""A second serving architecture for the harness's tests: written into a copy
of ``bench/models/`` as ``tiny_alt.py``, beside a configuration whose
``model_type`` is ``"tiny_alt"``. Its configuration keys are its own (no
``head_dim``: heads are ``d_model / n_head`` wide), it makes its own
weights, with norm scales that are not zero, and it gives no counts, so the
metrics that need them read nothing."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from bench.reference import dense_lm
from bench.weights import key_of


def program_config(cfg: dict):
    from repro.models.config import ModelConfig
    return ModelConfig(
        name=cfg["name"], family="dense", n_layers=cfg["n_layer"],
        d_model=cfg["d_model"], n_heads=cfg["n_head"],
        n_kv_heads=cfg["n_kv_head"], d_ff=cfg["d_ff"], vocab=cfg["vocab"],
        rope_theta=float(cfg["rope_theta"]), dtype=jnp.bfloat16,
        param_dtype=jnp.float32)


@partial(jax.jit, static_argnames=("L", "D", "H", "Hkv", "F", "V"))
def _weights(key, *, L, D, H, Hkv, F, V):
    Dh = D // H
    ks = iter(jax.random.split(key, 11))
    normal = lambda shape, scale: scale * jax.random.normal(next(ks), shape)
    return {
        "embed": {"table": normal((V, D), D ** -0.5)},
        "final_norm": {"scale": normal((D,), 0.1)},
        "layers": {
            "norm1": {"scale": normal((L, D), 0.1)},
            "norm2": {"scale": normal((L, D), 0.1)},
            "attn": {
                "wq": {"w": normal((L, D, H * Dh), D ** -0.5)},
                "wk": {"w": normal((L, D, Hkv * Dh), D ** -0.5)},
                "wv": {"w": normal((L, D, Hkv * Dh), D ** -0.5)},
                "wo": {"w": normal((L, H * Dh, D), (H * Dh) ** -0.5)},
            },
            "mlp": {"wi": normal((L, D, F), D ** -0.5),
                    "wg": normal((L, D, F), D ** -0.5),
                    "wo": normal((L, F, D), F ** -0.5)},
        },
    }


def weights(cfg: dict, seed: int):
    return _weights(key_of(seed, 7), L=cfg["n_layer"], D=cfg["d_model"],
                    H=cfg["n_head"], Hkv=cfg["n_kv_head"], F=cfg["d_ff"],
                    V=cfg["vocab"])


def reference_logits(params, cfg: dict, prompt, served, *, pad_to: int,
                     quant=None):
    # the same equations as the llama reference, under this file's keys
    as_llama = {"num_attention_heads": cfg["n_head"],
                "num_key_value_heads": cfg["n_kv_head"],
                "head_dim": cfg["d_model"] // cfg["n_head"],
                "rms_norm_eps": 1e-6, "rope_theta": cfg["rope_theta"]}
    return dense_lm.next_token_logits(params, as_llama, prompt, served,
                                      pad_to=pad_to, quant=quant)
