"""Operations and bytes of each step and kernel, counted from their shapes.

Counts are of the work the algorithm requires: multiply-adds count two
operations, only matrix products are counted (norms, softmax and other
elementwise work are left out), and work recomputed to save memory does not
count. ``cfg`` is a bench configuration file (``bench/configs/*.json``).
"""
from __future__ import annotations

KV_BYTES = 2   # the paged pool holds K and V in the bfloat16 compute dtype


def lm_dims(cfg: dict) -> dict:
    return dict(L=cfg["num_hidden_layers"], D=cfg["hidden_size"],
                H=cfg["num_attention_heads"], Hkv=cfg["num_key_value_heads"],
                Dh=cfg["head_dim"], F=cfg["intermediate_size"],
                V=cfg["vocab_size"])


def layer_weights(cfg: dict) -> int:
    """Matrix weights of one layer: q, k, v, o projections and SwiGLU."""
    d = lm_dims(cfg)
    D, H, Hkv, Dh, F = d["D"], d["H"], d["Hkv"], d["Dh"], d["F"]
    return D * H * Dh + 2 * D * Hkv * Dh + H * Dh * D + 3 * D * F


def decode_token_flops(cfg: dict, kv_len: int) -> int:
    """One decode step of one sequence that attends ``kv_len`` positions
    (its own new one included): all layers' projections and MLP, the
    attention scores and mix, and the tied unembedding."""
    d = lm_dims(cfg)
    return (2 * d["L"] * layer_weights(cfg) + 2 * d["D"] * d["V"]
            + 4 * d["L"] * d["H"] * d["Dh"] * kv_len)


def paged_attention_cost(cfg: dict, kv_lens) -> tuple[int, int]:
    """(flops, bytes) of one call of the paged decode-attention kernel for
    one layer, one query token per live sequence: every cached K and V row
    of each sequence is read once, its query read and its output written
    once. Dead slots (length 0) cost nothing."""
    d = lm_dims(cfg)
    H, Hkv, Dh = d["H"], d["Hkv"], d["Dh"]
    live = [int(n) for n in kv_lens if n > 0]
    flops = sum(4 * H * Dh * n for n in live)
    kv = sum(n * 2 * Hkv * Dh * KV_BYTES for n in live)
    qo = len(live) * 2 * H * Dh * KV_BYTES
    return flops, kv + qo


def lm_forward_flops(cfg: dict, batch: int, seq: int) -> int:
    """Forward pass of a training batch with the loss over every position:
    projections, MLP, causal attention and the tied unembedding."""
    d = lm_dims(cfg)
    tokens = batch * seq
    return (2 * tokens * (d["L"] * layer_weights(cfg) + d["D"] * d["V"])
            + 2 * batch * d["L"] * d["H"] * d["Dh"] * seq * seq)


def lm_bilevel_step_flops(cfg: dict, *, nodes: int, batch: int, seq: int,
                          J: int) -> int:
    """One step of the decentralized bilevel LM trainer over all nodes: per
    node the upper gradient and the lower gradient (a forward and a backward
    pass each, 3 forwards) and J Neumann Hessian-vector products
    (forward-over-reverse: twice a gradient, 6 forwards)."""
    fwd = lm_forward_flops(cfg, batch, seq)
    return nodes * (3 * fwd + 3 * fwd + J * 6 * fwd)


def logreg_step_flops(*, nodes: int, batch: int, d: int, c: int,
                      J: int) -> int:
    """One MDBO step of the logistic-regression problem over all nodes.

    Per node, with ``f = 2 * batch * d * c`` (the one matrix product
    ``a @ y``): the upper gradient and the lower gradient (product and its
    transpose, 2f each; the features take no gradient), and J Neumann
    Hessian-vector products (4f each). Gossip: four products of the K x K
    mixing matrix with x, y and their trackers."""
    f = 2 * batch * d * c
    per_node = 2 * f + 2 * f + J * 4 * f
    mix = 2 * nodes * nodes * (2 * d + 2 * d * c)
    return nodes * per_node + mix
