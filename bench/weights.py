"""Weights and data made from ``--seed`` on the device, in one jitted call.

The benchmark makes them, not the program, so the plain references can be
given the very same arrays. The LM weights are laid out as the program's
parameter tree expects them (``{"embed", "final_norm", "layers"}``, layers
stacked on a leading axis) and drawn at the scales of the program's own
initialiser; the norm scales are zero because the model applies
``x * (1 + w)``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from bench.flops import lm_dims


def key_of(seed: int, salt: int = 0) -> jax.Array:
    """A PRNG key that keeps all of a seed's bits (seeds pass 2**32)."""
    seed = int(seed)
    k = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(k, seed >> 32), salt)


@partial(jax.jit, static_argnames=("L", "D", "H", "Hkv", "Dh", "F", "V",
                                   "dtype"))
def _dense_lm(key, *, L, D, H, Hkv, Dh, F, V, dtype):
    ks = jax.random.split(key, 8)

    def normal(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                * fan_in ** -0.5).astype(dtype)

    zeros = lambda *s: jnp.zeros(s, dtype)
    return {
        "embed": {"table": normal(ks[0], (V, D), D)},
        "final_norm": {"scale": zeros(D)},
        "layers": {
            "norm1": {"scale": zeros(L, D)},
            "norm2": {"scale": zeros(L, D)},
            "attn": {
                "wq": {"w": normal(ks[1], (L, D, H * Dh), D)},
                "wk": {"w": normal(ks[2], (L, D, Hkv * Dh), D)},
                "wv": {"w": normal(ks[3], (L, D, Hkv * Dh), D)},
                "wo": {"w": normal(ks[4], (L, H * Dh, D), H * Dh)},
            },
            "mlp": {
                "wi": normal(ks[5], (L, D, F), D),
                "wg": normal(ks[6], (L, D, F), D),
                "wo": normal(ks[7], (L, F, D), F),
            },
        },
    }


def dense_lm(cfg: dict, seed: int):
    """Random weights of a dense (llama-style) LM config, in the config's
    parameter dtype, made on the default device."""
    return _dense_lm(key_of(seed, 1), dtype=jnp.dtype(cfg["param_dtype"]),
                     **lm_dims(cfg))


@partial(jax.jit, static_argnames=("n", "d", "c", "noise"))
def _classification(key, *, n, d, c, noise):
    kc, kl, kf = jax.random.split(key, 3)
    centers = jax.random.normal(kc, (c, d))
    centers = centers / jnp.linalg.norm(centers, axis=1, keepdims=True)
    labels = jax.random.randint(kl, (n,), 0, c)
    feats = centers[labels] + noise * jax.random.normal(kf, (n, d))
    # libsvm-style scaling of every feature into [-1, 1]
    return feats / jnp.max(jnp.abs(feats)), labels.astype(jnp.int32)


@partial(jax.jit, static_argnames=("K", "val_frac"))
def _split(key, a, b, *, K, val_frac):
    n = a.shape[0]
    perm = jax.random.permutation(key, n)
    n_val = int(n * val_frac)
    va, tr = perm[:n_val], perm[n_val:]
    per_tr, per_va = tr.shape[0] // K, n_val // K
    tr, va = tr[:per_tr * K].reshape(K, per_tr), va[:per_va * K].reshape(
        K, per_va)
    return a[tr], b[tr], a[va], b[va]


def classification(data: dict, K: int, seed: int):
    """Class-cluster data shaped like ``data`` (n, d, classes), split into
    train/validation by ``val_frac`` and dealt evenly to K nodes:
    ``(tr_a [K, n_tr, d], tr_b [K, n_tr], va_a [K, n_va, d], va_b)``."""
    a, b = _classification(key_of(seed, 2), n=data["n"], d=data["d"],
                           c=data["classes"], noise=float(data["noise"]))
    return _split(key_of(seed, 3), a, b, K=K,
                  val_frac=float(data["val_frac"]))
