"""End-to-end behaviour tests for the paper's system.

1. Full §6-style experiment at smoke scale: all four algorithms on the same
   synthetic logreg hyperopt task — VRDBO/MDBO correctness + baselines.
2. Decentralized bilevel LM training (the production trainer, reduced arch):
   lower loss decreases, nodes reach consensus, hyperparameters adapt.
3. Roofline utilities: HLO collective parsing on a synthetic module.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.core import (HParams, HypergradConfig, logreg_hyperopt, ring, run)
from repro.data import (NodeSampler, make_classification, shard_to_nodes,
                        train_val_split)


def test_paper_experiment_all_algorithms_end_to_end():
    K, d, J = 4, 20, 5
    ds = make_classification(n=1600, d=d, seed=3)
    tr, va = train_val_split(ds)
    sampler = NodeSampler(shard_to_nodes(tr, K), shard_to_nodes(va, K),
                          batch=64, J=J, seed=3)
    prob = logreg_hyperopt(d=d, lip_gy=5.0)
    cfg = HypergradConfig(J=J, lip_gy=5.0)
    eval_batch = sampler.eval_batch()
    finals = {}
    for algo, hp in [("dsbo", HParams(eta=0.1)),
                     ("gdsbo", HParams(eta=0.1)),
                     ("mdbo", HParams(eta=0.1)),
                     ("vrdbo", HParams(eta=0.33, alpha1=5.0, alpha2=5.0))]:
        r = run(prob, cfg, hp, ring(K), algo, sampler, eval_batch,
                steps=50, eval_every=50)
        finals[algo] = r.upper_loss[-1]
        assert r.upper_loss[-1] < r.upper_loss[0], algo
        assert r.consensus_y[-1] < 1.0, algo
    # every algorithm lands in the same basin on this easy task
    assert max(finals.values()) - min(finals.values()) < 0.5, finals


def test_decentralized_bilevel_lm_training():
    from repro.configs import get
    from repro.core.common import consensus_error, replicate
    from repro.models import loss_fn
    from repro.train import (TrainerConfig, make_mix, make_step_batch,
                             make_step_fns)
    from functools import partial

    cfg = get("smollm-360m").reduced()
    tc = TrainerConfig(algo="mdbo", J=1, mix="ring")
    problem, init_fn, step_fn = make_step_fns(cfg, tc)
    K = 4
    mix = make_mix(tc, K)
    key = jax.random.PRNGKey(0)
    X0 = replicate(problem.init_x(key), K)
    Y0 = replicate(problem.init_y(key), K)
    # progress is judged on a FIXED held-out batch (per-step batches are too
    # noisy for a 6-step first-vs-last comparison)
    kfix, key = jax.random.split(key)
    fixed = jax.tree.map(
        lambda a: a[0], make_step_batch(cfg, tc, kfix, K, 2, 16)["g"])

    def eval_loss(st):
        return float(loss_fn(cfg, jax.tree.map(lambda a: a[0], st.y), fixed))

    batch = make_step_batch(cfg, tc, key, K, per_node=2, seq=16)
    st = init_fn(mix, X0, Y0, batch, jax.random.split(key, K))
    stepj = jax.jit(partial(step_fn, mix))
    first = eval_loss(st)
    for t in range(6):
        key, kb = jax.random.split(key)
        batch = make_step_batch(cfg, tc, kb, K, per_node=2, seq=16)
        st = stepj(st, batch, jax.random.split(kb, K))
    assert eval_loss(st) < first
    assert float(consensus_error(st.x)) < 1e-2
    # the hypergradient pipeline delivers (tiny but nonzero) x-tracking
    # signal; x itself moves below f32 resolution at this scale/step count,
    # so assert on the tracker Z^F̃ (see test_logreg_bilevel for x movement)
    assert float(jnp.abs(st.zf).max()) > 0.0
    assert bool(jnp.all(jnp.isfinite(st.zf)))


def test_collective_parser_on_synthetic_hlo():
    from repro.launch.roofline import collective_bytes, shape_bytes
    hlo = """
  %ag = bf16[16,128]{1,0} all-gather(%p0), replica_groups=...
  %ar.1 = f32[4,4]{1,0} all-reduce-start(%x), to_apply=%add
  %done = f32[4,4]{1,0} all-reduce-done(%ar.1)
  %cp = (f32[8]{0}, f32[8]{0}) collective-permute(%a, %b)
  %fusion.1 = f32[2]{0} fusion(%ag), kind=kLoop
"""
    out = collective_bytes(hlo)
    assert out["all-gather_bytes"] == 16 * 128 * 2
    assert out["all-reduce_bytes"] == 4 * 4 * 4
    assert out["collective-permute_bytes"] == 2 * 8 * 4
    assert out["total_bytes"] == sum(
        v for k, v in out.items()
        if k.endswith("_bytes") and k != "total_bytes")
    assert shape_bytes("(f32[2,2], bf16[4])") == 16 + 8


def test_roofline_terms_and_dominance():
    from repro.launch.roofline import TARGET_KIND, Roofline, peaks
    pk = peaks(TARGET_KIND)
    rl = Roofline(flops_per_device=pk.flops, hbm_bytes_per_device=pk.hbm_bw,
                  collective_bytes_per_device=2 * pk.link_bw)
    assert rl.t_compute == pytest.approx(1.0)
    assert rl.t_memory == pytest.approx(1.0)
    assert rl.t_collective == pytest.approx(2.0)
    assert rl.dominant == "collective"
    with pytest.raises(ValueError, match="no published peaks"):
        _ = Roofline(1.0, 1.0, 1.0, device_kind="cpu").t_compute


def test_model_flops_accounting():
    from repro.configs import SHAPES, get
    from repro.launch.roofline import model_flops
    spec = get("qwen2.5-3b")
    n = spec.config.param_count(active_only=True)
    assert model_flops(spec, SHAPES["train_4k"], 256) == pytest.approx(
        6.0 * n * 256 * 4096)
    assert model_flops(spec, SHAPES["decode_32k"], 256) == pytest.approx(
        2.0 * n * 128)
    # MoE: active params < total params
    moe = get("phi3.5-moe-42b-a6.6b")
    assert moe.config.param_count(active_only=True) < \
        moe.config.param_count()
