"""Plain float32 forward pass of a dense llama-style LM (the smollm family).

RMSNorm with scale ``1 + w``, rotary embeddings (rotate-half form),
grouped-query causal softmax attention, SwiGLU MLP and a tied embedding, in
straightforward ``jax.numpy`` with every product at HIGHEST precision. It
imports nothing of the program; the weights it reads are the benchmark's own
(``bench/weights.py``).

``quant="fp8"`` is the control of the serving check: the same pass with every
matmul operand rounded to float8 e4m3, one scale per tensor.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0   # largest finite float8_e4m3fn


def _round(x, quant):
    if quant is None:
        return x
    if quant != "fp8":
        raise ValueError(f"unknown quant {quant!r}")
    s = jnp.max(jnp.abs(x)) / F8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(eq, a, b, quant):
    return jnp.einsum(eq, _round(a, quant), _round(b, quant), precision=HI)


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)


def _rope(x, theta):
    """x [S, heads, Dh]; position of row s is s."""
    S, _, dh = x.shape
    half = dh // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freq
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("H", "Hkv", "Dh", "eps", "theta", "quant"))
def hidden(params, tokens, *, H, Hkv, Dh, eps, theta, quant=None):
    """Final-normed hidden states [S, D] of one sequence ``tokens`` [S]."""
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    S = tokens.shape[0]
    G = H // Hkv
    causal = jnp.tril(jnp.ones((S, S), bool))
    x = f32(params["embed"]["table"])[tokens]

    def layer(x, lp):
        lp = f32(lp)
        h = _rms(x, lp["norm1"]["scale"], eps)
        a = lp["attn"]
        q = _rope(_mm("sd,de->se", h, a["wq"]["w"], quant).reshape(S, H, Dh),
                  theta)
        k = _rope(_mm("sd,de->se", h, a["wk"]["w"], quant).reshape(
            S, Hkv, Dh), theta)
        v = _mm("sd,de->se", h, a["wv"]["w"], quant).reshape(S, Hkv, Dh)
        qg = q.reshape(S, Hkv, G, Dh) * Dh ** -0.5
        s = _mm("shgd,thd->hgst", qg, k, quant)
        s = jnp.where(causal, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = _mm("hgst,thd->shgd", p, v, quant).reshape(S, H * Dh)
        x = x + _mm("se,ed->sd", o, a["wo"]["w"], quant)
        h2 = _rms(x, lp["norm2"]["scale"], eps)
        m = lp["mlp"]
        up = (jax.nn.silu(_mm("sd,df->sf", h2, m["wg"], quant))
              * _mm("sd,df->sf", h2, m["wi"], quant))
        return x + _mm("sf,fd->sd", up, m["wo"], quant), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    return _rms(x, params["final_norm"]["scale"].astype(jnp.float32), eps)


@partial(jax.jit, static_argnames=("quant",))
def logits(params, h, *, quant=None):
    """Logits [n, V] of hidden rows ``h`` [n, D] through the tied table."""
    table = params["embed"]["table"].astype(jnp.float32)
    return _mm("nd,vd->nv", h, table, quant)


def dims(cfg: dict) -> dict:
    return dict(H=cfg["num_attention_heads"], Hkv=cfg["num_key_value_heads"],
                Dh=cfg["head_dim"], eps=float(cfg["rms_norm_eps"]),
                theta=float(cfg["rope_theta"]))


def next_token_logits(params, cfg: dict, prompt, served, *, pad_to: int,
                      quant=None) -> jax.Array:
    """Logits [n, V] at the positions where ``served`` (n tokens) were
    produced, from one pass over ``prompt + served[:-1]`` padded to
    ``pad_to`` (causal: the padding changes no row that is read)."""
    seq = np.concatenate([np.asarray(prompt), np.asarray(served[:-1])])
    if len(seq) > pad_to:
        raise ValueError(f"sequence of {len(seq)} > pad_to {pad_to}")
    toks = np.zeros(pad_to, np.int32)
    toks[:len(seq)] = seq
    h = hidden(params, jnp.asarray(toks), quant=quant, **dims(cfg))
    # rows padded to a multiple of 256, so one program serves every length
    n = len(served)
    rows = len(prompt) - 1 + np.arange(-(-n // 256) * 256)
    rows = np.minimum(rows, pad_to - 1)
    return logits(params, h[jnp.asarray(rows)], quant=quant)[:n]
