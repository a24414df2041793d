"""Faults planted in the program under test, to see ``correct`` come out
false. Each is a context manager that patches the program for the runs
made inside it and restores it after; the benchmark's own runs plant none.

* ``frozen_state``  — the algorithm's step returns its state unchanged;
* ``half_batch``    — every node draws half of its minibatch, and each mean
  is taken over that half;
* ``no_gossip``     — the exchange between nodes is left out (the mixing
  operator returns its input);
* ``token_altered`` — the paged decode chunk reports, for every slot, the
  first token of each chunk one id higher than it produced.
"""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def _patched(obj, name: str, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


@contextlib.contextmanager
def frozen_state():
    from repro.core import engine
    saved = dict(engine.ALGORITHMS)
    for name, alg in saved.items():
        engine.ALGORITHMS[name] = engine.Algorithm(
            alg.init, lambda problem, cfg, hp, mix, state, batch, keys: state)
    try:
        yield
    finally:
        engine.ALGORITHMS.update(saved)


@contextlib.contextmanager
def half_batch():
    import repro.data
    orig = repro.data.make_device_sampler

    def sampler(train_nodes, val_nodes, batch, J):
        return orig(train_nodes, val_nodes, max(batch // 2, 1), J)

    with _patched(repro.data, "make_device_sampler", sampler):
        yield


@contextlib.contextmanager
def no_gossip():
    from repro.core import engine

    with _patched(engine, "make_mix", lambda name, **kw: (lambda tree: tree)):
        yield


@contextlib.contextmanager
def token_altered():
    from repro.serve import engine as serve_engine

    orig = serve_engine.make_paged_kernel_decode

    def make(cfg, *a, **kw):
        chunk = orig(cfg, *a, **kw)

        def altered(*args):
            out = chunk(*args)
            tokens = out[5]
            tokens = tokens.at[0].set((tokens[0] + 1) % cfg.vocab)
            return out[:5] + (tokens,) + out[6:]

        return altered

    with _patched(serve_engine, "make_paged_kernel_decode", make):
        yield


FAULTS = {"frozen_state": frozen_state, "half_batch": half_batch,
          "no_gossip": no_gossip, "token_altered": token_altered}
