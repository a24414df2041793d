"""Operation and byte counts against hand counts at small shapes, and
against XLA's cost analysis where that is sound (no loops to undercount)."""
import jax
import jax.numpy as jnp
import pytest

from bench import flops, peaks
from bench.reference import bilevel, dense_lm

TINY = {"num_hidden_layers": 2, "hidden_size": 8, "num_attention_heads": 2,
        "num_key_value_heads": 1, "head_dim": 4, "intermediate_size": 16,
        "vocab_size": 32}


def test_hand_counts():
    # q 8x8 + k,v 2 x 8x4 + o 8x8 + MLP 3 x 8x16
    assert flops.layer_weights(TINY) == 64 + 64 + 64 + 384
    # 2 L w + 2 D V + 4 L H Dh kv
    assert flops.decode_token_flops(TINY, 5) == 2304 + 512 + 320
    # two live sequences of 3 and 5 positions, one dead slot
    f, b = flops.paged_attention_cost(TINY, [3, 0, 5])
    assert f == 4 * 2 * 4 * 8
    assert b == 8 * 2 * 1 * 4 * 2 + 2 * 2 * 2 * 4 * 2
    fwd = flops.lm_forward_flops(TINY, batch=2, seq=3)
    assert fwd == 2 * 6 * (2 * 576 + 8 * 32) + 2 * 2 * 2 * 2 * 4 * 9
    assert flops.lm_bilevel_step_flops(TINY, nodes=4, batch=2, seq=3,
                                       J=2) == 4 * 18 * fwd
    # f = 2 B d c = 48; per node 12 f; mix 2 K^2 (2d + 2dc)
    assert flops.logreg_step_flops(nodes=2, batch=3, d=4, c=2,
                                   J=2) == 2 * 576 + 192


def _xla_flops(fn, *args):
    return jax.jit(fn).lower(*args).compile().cost_analysis()["flops"]


def test_logreg_gradient_against_xla():
    """2 f for a lower-level gradient: XLA adds only elementwise work."""
    B, d, c = 50, 123, 2
    a = jnp.ones((B, d))
    b = jnp.zeros((B,), jnp.int32)
    x, y = jnp.zeros((d,)), jnp.ones((d, c))
    got = _xla_flops(jax.grad(bilevel.lower, argnums=1), x, y, a, b)
    mine = 2 * (2 * B * d * c)
    assert mine <= got <= 3 * mine


def test_decode_token_against_xla():
    """One token through a one-layer model: XLA's count of the reference
    pass lies within 1.5x of the matmul count (norms, RoPE, softmax)."""
    cfg = {"num_hidden_layers": 1, "hidden_size": 64,
           "num_attention_heads": 4, "num_key_value_heads": 2,
           "head_dim": 16, "intermediate_size": 128, "vocab_size": 256,
           "rms_norm_eps": 1e-6, "rope_theta": 1e4, "param_dtype": "float32"}
    from bench import weights
    params = weights.dense_lm(cfg, 0)
    toks = jnp.zeros((1,), jnp.int32)

    def one(p, t):
        h = dense_lm.hidden.__wrapped__(p, t, **dense_lm.dims(cfg))
        return dense_lm.logits.__wrapped__(p, h)

    got = _xla_flops(one, params, toks)
    mine = flops.decode_token_flops(cfg, 1)
    assert mine <= got <= 1.5 * mine


def test_unknown_device_is_an_error():
    assert peaks.peaks("TPU v5 lite").hbm_bw == 819e9
    with pytest.raises(ValueError):
        peaks.peaks("cpu")
