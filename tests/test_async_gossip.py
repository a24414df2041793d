"""The async_gossip backend's contracts.

* τ=0 reproduces synchronous ring gossip **bitwise** (drop draws and all —
  forced delivery makes the `where`s select exactly the fresh exchange), in
  single-process mode against ``ring_rolled`` and, in a forced-host-device
  subprocess, against ``ring_local`` under shard_map.
* The engine's fused==per_step bitwise contract extends to τ>0 with active
  drops (the caches/ages/keys ride the scan carry), including the
  EF21-compressed composition.
* τ>0 still converges on the §6 logreg workload (staleness degrades, not
  destroys, progress), and a used neighbor value is never older than τ.
* The shard-local EF21 ``(W−I)·h`` operator matches the dense one.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import HParams, HypergradConfig, logreg_hyperopt, ring
from repro.core.async_gossip import AsyncGossipMix, expected_staleness
from repro.core.compression import dense_wmi, ring_wmi_rolled
from repro.core.engine import Engine
from repro.data import (make_classification, make_device_sampler,
                        shard_to_nodes, train_val_split)

ROOT = os.path.join(os.path.dirname(__file__), "..")
K, D, J = 4, 12, 3


@pytest.fixture(scope="module")
def setup():
    ds = make_classification(n=800, d=D, c=2, seed=1)
    tr, va = train_val_split(ds, 0.3, seed=1)
    sample = make_device_sampler(shard_to_nodes(tr, K), shard_to_nodes(va, K),
                                 batch=16, J=J)
    prob = logreg_hyperopt(d=D, c=2, lip_gy=5.0)
    cfg = HypergradConfig(J=J, lip_gy=5.0, randomize=True)
    hp = HParams(eta=0.1)
    eval_batch = {"a": jnp.asarray(va.a[:128]), "b": jnp.asarray(va.b[:128])}
    return prob, cfg, hp, sample, eval_batch


def _assert_trees_bitwise_equal(a, b):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


@pytest.mark.parametrize("algo", ["mdbo", "vrdbo"])
def test_tau0_bitwise_equals_ring_rolled(setup, algo):
    """Synchronous degeneration: τ=0 forces every edge fresh, even at
    drop_prob 0.7 — bit-identical to the W-free rolled ring backend."""
    prob, cfg, hp, sample, eval_batch = setup
    out = {}
    for mix, mk in (("ring_rolled", None),
                    ("async_gossip", {"tau": 0, "drop_prob": 0.7})):
        eng = Engine(prob, cfg, hp, ring(K), algo=algo, mix=mix,
                     dispatch="fused", mix_kwargs=mk)
        out[mix] = eng.run(sample, eval_batch, steps=7, eval_every=3,
                           seed=0, return_state=True)
    (rr, sr), (ra, sa) = out["ring_rolled"], out["async_gossip"]
    _assert_trees_bitwise_equal(sr, sa)
    assert rr.upper_loss == ra.upper_loss


@pytest.mark.parametrize("mix_kwargs", [
    {"tau": 3, "drop_prob": 0.4, "seed": 5},
    {"tau": 2, "drop_prob": 0.3, "error_feedback": True, "ratio": 0.25},
])
def test_fused_bitwise_equals_per_step_tau_positive(setup, mix_kwargs):
    """The engine's bitwise contract extends to async gossip with live
    staleness/drops (and to the EF21-compressed composition): the neighbor
    caches, ages and drop keys thread through the scan carry."""
    prob, cfg, hp, sample, eval_batch = setup
    out = {}
    for dispatch in ("fused", "per_step"):
        eng = Engine(prob, cfg, hp, ring(K), algo="mdbo", mix="async_gossip",
                     dispatch=dispatch, mix_kwargs=mix_kwargs)
        out[dispatch] = eng.run(sample, eval_batch, steps=7, eval_every=3,
                                seed=0, return_state=True)
    (rf, sf), (rp, sp) = out["fused"], out["per_step"]
    _assert_trees_bitwise_equal(sf, sp)
    assert rf.upper_loss == rp.upper_loss


def test_tau_positive_convergence_smoke(setup):
    """§6 logreg: stale-by-3 gossip with 40% drops still drives the loss
    down, landing near the synchronous run (staleness is a perturbation,
    not a divergence)."""
    prob, cfg, hp, sample, eval_batch = setup
    final = {}
    for mix, mk in (("ring_rolled", None),
                    ("async_gossip", {"tau": 3, "drop_prob": 0.4})):
        eng = Engine(prob, cfg, hp, ring(K), algo="mdbo", mix=mix,
                     mix_kwargs=mk)
        res = eng.run(sample, eval_batch, steps=40, eval_every=10, seed=0)
        final[mix] = res
    r = final["async_gossip"]
    assert r.upper_loss[-1] < r.upper_loss[0]          # actually progresses
    assert r.consensus_x[-1] < 1e-3                    # consensus bounded
    assert abs(r.upper_loss[-1]
               - final["ring_rolled"].upper_loss[-1]) < 0.02


def test_staleness_never_exceeds_tau():
    """The stale-by-τ bound: after every apply, every edge age ≤ τ, even at
    90% drops — delivery is forced before a value can overage."""
    tau, n = 3, 6
    mix = AsyncGossipMix(n, tau=tau, drop_prob=0.9, seed=0)
    tree = {"w": jnp.arange(n * 2, dtype=jnp.float32).reshape(n, 2)}
    st = mix.state0(jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree), 0)
    for t in range(50):
        tree = {"w": tree["w"] * 0.9 + t}
        _, st = mix.apply(tree, st)
        assert int(st["age_left"].max()) <= tau
        assert int(st["age_right"].max()) <= tau


def test_rejects_degenerate_rings_and_negative_tau():
    with pytest.raises(ValueError):
        AsyncGossipMix(2)
    with pytest.raises(ValueError):
        AsyncGossipMix(4, tau=-1)


def test_rejects_non_ring_topology():
    """async_gossip is ring-only: a star W must raise, not silently remix
    on ring neighbors."""
    from repro.core.engine import make_mix
    from repro.core.topology import star
    with pytest.raises(ValueError, match="ring"):
        make_mix("async_gossip", weights=star(5).weights, K=5)
    make_mix("async_gossip", weights=ring(5).weights, K=5)  # ring W is fine


def test_expected_staleness_closed_form():
    """Analytic stationary mean of the age chain vs direct simulation."""
    assert expected_staleness(0, 0.9) == 0.0
    assert expected_staleness(5, 0.0) == 0.0
    tau, q, rng = 3, 0.6, np.random.default_rng(0)
    age, seen = 0, []
    for _ in range(200_000):
        if age >= tau or rng.random() >= q:
            age = 0
        else:
            age += 1
        seen.append(age)
    assert abs(np.mean(seen) - expected_staleness(tau, q)) < 0.01


def test_adaptive_deadline_pins_drop_rate():
    """The adaptive deadline is the q-quantile of the delay tail: ~1-q of
    sampled deliveries miss it, and drop_prob() at that deadline agrees."""
    from repro.core.topology import EdgeDelayModel
    model = EdgeDelayModel(base_s=2e-3, straggler_prob=0.3,
                           straggler_scale_s=40e-3)
    rng = np.random.default_rng(0)
    d90 = model.adaptive_deadline(0.90, n_edges=16, rounds=2000, rng=rng)
    d99 = model.adaptive_deadline(0.99, n_edges=16, rounds=2000, rng=rng)
    assert d99 > d90 > 2e-3  # monotone in q, above the deterministic base
    # empirical miss rate at the q-deadline is ~1-q
    delays = model.sample(np.random.default_rng(1), 16, 2000)
    assert abs((delays > d90).mean() - 0.10) < 0.02
    # and the analytic per-edge drop prob the async mix consumes agrees
    assert abs(model.drop_prob(d90, 16).mean() - 0.10) < 0.02


def test_adaptive_deadline_from_observed_delays():
    """Operating on measured delays (no model sampling): plain quantile."""
    from repro.core.topology import EdgeDelayModel
    model = EdgeDelayModel()
    obs = np.array([1.0, 2.0, 3.0, 4.0, 5.0, 6.0, 7.0, 8.0, 9.0, 10.0])
    assert model.adaptive_deadline(0.5, observed=obs) == pytest.approx(5.5)
    with pytest.raises(ValueError, match="quantile"):
        model.adaptive_deadline(1.5, observed=obs)
    with pytest.raises(ValueError, match="n_edges"):
        model.adaptive_deadline(0.9)


def test_ring_wmi_rolled_matches_dense():
    """(W−I)·h via rolls == the dense einsum for the ring W."""
    W = ring(6).weights
    h = {"a": jnp.asarray(np.random.default_rng(0).normal(size=(6, 4, 3)),
                          jnp.float32)}
    out_r = ring_wmi_rolled(1.0 / 3.0)(h)
    out_d = dense_wmi(W)(h)
    np.testing.assert_allclose(np.asarray(out_r["a"]), np.asarray(out_d["a"]),
                               rtol=1e-6, atol=1e-6)


SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np, jax, jax.numpy as jnp
from repro.core import HParams, HypergradConfig, quadratic_problem, ring
from repro.core.engine import Engine

K, J = 4, 4
prob, _ = quadratic_problem(dx=3, dy=5, noise=0.05)
cfg = HypergradConfig(J=J, lip_gy=prob.lip_gy)
hp = HParams(eta=0.1, beta1=0.05, beta2=0.2)

def sample_batch(k):
    kf, kg, kh = jax.random.split(k, 3)
    return {"f": jax.random.split(kf, K), "g": jax.random.split(kg, K),
            "h": jax.vmap(lambda kk: jax.random.split(kk, J))(
                jax.random.split(kh, K))}

from repro.launch.mesh import make_mesh
mesh = make_mesh((4,), ("data",), jax.devices())

def leaves_equal(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))

def run(mix, dispatch="fused", mix_kwargs=None):
    eng = Engine(prob, cfg, hp, ring(K), algo="mdbo", mix=mix,
                 dispatch=dispatch, mesh=mesh, mix_kwargs=mix_kwargs)
    return eng.run(sample_batch, jax.random.PRNGKey(9), steps=7,
                   eval_every=3, seed=1, return_state=True)[1]

# async tau=0 under shard_map == synchronous ring_local, bitwise
assert leaves_equal(run("ring_local"),
                    run("async_gossip", mix_kwargs={"tau": 0,
                                                    "drop_prob": 0.5}))
# async tau>0 under shard_map: fused == per_step bitwise (sharded carry)
mk = {"tau": 2, "drop_prob": 0.4}
assert leaves_equal(run("async_gossip", "fused", mk),
                    run("async_gossip", "per_step", mk))
# shard-local EF21 under ring_local: fused == per_step bitwise
mk = {"error_feedback": True, "ratio": 0.25}
assert leaves_equal(run("ring_local", "fused", mk),
                    run("ring_local", "per_step", mk))
# ...and it matches the dense-EF reference numerically
dense = Engine(prob, cfg, hp, ring(K), algo="mdbo", mix="compressed_topk",
               mix_kwargs=mk).run(sample_batch, jax.random.PRNGKey(9),
                                  steps=7, eval_every=3, seed=1,
                                  return_state=True)[1]
for a, b in zip(jax.tree.leaves(run("ring_local", "fused", mk)),
                jax.tree.leaves(dense)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-5, atol=2e-6)
print("ASYNC_SHARD_LOCAL_OK")
"""


@pytest.mark.slow
def test_shard_local_async_and_ef_contracts():
    """Forced-host-device subprocess: async τ=0 == ring_local bitwise,
    fused == per_step with the carry sharded one-node-per-shard, and
    shard-local EF21 == the dense-EF reference."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, env=env, cwd=ROOT, timeout=600)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "ASYNC_SHARD_LOCAL_OK" in r.stdout
