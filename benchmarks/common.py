"""Shared setup for the paper-reproduction benchmarks (§6 protocol).

Datasets: LIBSVM a9a/ijcnn1/covtype are unavailable offline — synthetic
classification sets with matched dimensionality stand in (see DESIGN.md §5).
Protocol knobs follow the paper exactly: 70/30 split, batch 400/K per node,
J=10, η=0.1 (0.33 for VRDBO), β1=β2=1, α1=α2=1 (5 for VRDBO), ring network.
"""
from __future__ import annotations

import json
import os
import subprocess
import time

from repro.core import HParams, HypergradConfig, logreg_hyperopt, ring
from repro.data import (NodeSampler, make_classification, shard_to_nodes,
                        train_val_split)

RESULTS = os.path.join(os.path.dirname(__file__), "results")

DATASETS = {
    # name: (n, d) mirroring a9a / ijcnn1 scale (covtype-scale is CPU-heavy;
    # use --full to enable its 64-dim stand-in at 40k samples)
    "a9a-syn": (8_000, 123),
    "ijcnn1-syn": (10_000, 22),
}

PAPER_HP = {
    "dsbo": HParams(eta=0.1, alpha1=1.0, alpha2=1.0, beta1=1.0, beta2=1.0),
    "gdsbo": HParams(eta=0.1, alpha1=1.0, alpha2=1.0, beta1=1.0, beta2=1.0),
    "mdbo": HParams(eta=0.1, alpha1=1.0, alpha2=1.0, beta1=1.0, beta2=1.0),
    "vrdbo": HParams(eta=0.33, alpha1=5.0, alpha2=5.0, beta1=1.0, beta2=1.0),
}
J = 10


def build(dataset: str, K: int, batch_total: int = 400, seed: int = 0):
    n, d = DATASETS[dataset]
    ds = make_classification(n=n, d=d, c=2, seed=seed)
    tr, va = train_val_split(ds, 0.3, seed=seed)
    sampler = NodeSampler(shard_to_nodes(tr, K), shard_to_nodes(va, K),
                          batch=max(batch_total // K, 1), J=J, seed=seed)
    prob = logreg_hyperopt(d=d, c=2, lip_gy=5.0)
    cfg = HypergradConfig(J=J, lip_gy=5.0, randomize=True)
    return prob, cfg, sampler, ring(K)


def provenance() -> dict:
    """Attribution stamp for a BENCH record: git sha (+dirty flag), jax
    version, device kind, UTC timestamp. The git sha degrades to
    ``"unknown"`` outside a git checkout; a device JAX cannot name fails."""
    import jax
    sha = "unknown"
    try:
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            cwd=os.path.dirname(os.path.abspath(__file__)), timeout=10)
        if out.returncode == 0:
            sha = out.stdout.strip()
            dirty = subprocess.run(
                ["git", "status", "--porcelain"], capture_output=True,
                text=True, cwd=os.path.dirname(os.path.abspath(__file__)),
                timeout=10)
            if dirty.returncode == 0 and dirty.stdout.strip():
                sha += "-dirty"
    except (OSError, subprocess.TimeoutExpired):
        pass
    return {
        "git_sha": sha,
        "jax_version": jax.__version__,
        "device_kind": jax.devices()[0].device_kind,
        "backend": jax.default_backend(),
        "timestamp_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }


def write_bench_json(name: str, payload: dict) -> str:
    """Write ``benchmarks/results/BENCH_<name>.json`` — the machine-readable
    perf record tracked across PRs (steps/sec, tokens/sec, consensus error,
    wall-clock curves; whatever the bench measures). Every record is stamped
    with :func:`provenance` (git sha, jax version, device kind, timestamp)
    so ``run.py --compare`` trajectories are attributable. Returns the
    path."""
    payload = dict(payload)
    payload.setdefault("provenance", provenance())
    os.makedirs(RESULTS, exist_ok=True)
    path = os.path.join(RESULTS, f"BENCH_{name}.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True, default=float)
        f.write("\n")
    return path


def write_csv(path: str, rows: list[dict]):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    if not rows:
        return
    keys = list(rows[0])
    with open(path, "w") as f:
        f.write(",".join(keys) + "\n")
        for r in rows:
            f.write(",".join(str(r[k]) for k in keys) + "\n")
