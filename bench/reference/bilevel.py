"""Plain MDBO (Algorithm 1 of arXiv 2206.15025) on the paper's logistic
regression hyperparameter problem, in float32 at HIGHEST precision.

It repeats, from the same seed, what a fused ``Engine(algo="mdbo")`` run with
a device-resident node sampler does: the run's key discipline (two
independent subkeys per step, one for the minibatch and one for the per-node
Neumann level), the uniform-with-replacement draws of the sampler, the
randomised Neumann hypergradient and gradient tracking over a dense mixing
matrix. It imports nothing of the program.

``dtype=jnp.bfloat16`` is the control of the check: the same run with data,
parameters and state held in bfloat16.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def _xent(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def upper(x, y, a, b):
    return _xent(jnp.matmul(a, y, precision=HI), b)


def lower(x, y, a, b):
    reg = jnp.mean(jnp.exp(x)[:, None] * y ** 2)
    return _xent(jnp.matmul(a, y, precision=HI), b) + reg


def _draw(key, feats, labels, batch):
    idx = jax.random.randint(key, (feats.shape[0], batch), 0, feats.shape[1])
    take = jax.vmap(lambda f, i: f[i])
    return take(feats, idx), take(labels, idx)


def sample(key, data, *, batch: int, J: int):
    """One step's draws: f on validation, g and the J Neumann batches on
    training data, each ``(a [K, ..., batch, d], b [K, ..., batch])``."""
    tr_a, tr_b, va_a, va_b = data
    kf, kg, kh = jax.random.split(key, 3)
    ha, hb = jax.vmap(lambda k: _draw(k, tr_a, tr_b, batch))(
        jax.random.split(kh, J))
    return {"f": _draw(kf, va_a, va_b, batch),
            "g": _draw(kg, tr_a, tr_b, batch),
            "h": (jnp.swapaxes(ha, 0, 1), jnp.swapaxes(hb, 0, 1))}


def _hypergrad(x, y, fb, gb, hb, key, *, J: int, L: float):
    gx, gy = jax.grad(upper, argnums=(0, 1))(x, y, *fb)
    jt = jax.random.randint(key, (), 0, J)
    grad_y = lambda a, b: (lambda yy: jax.grad(lower, argnums=1)(x, yy, a, b))
    v = gy
    for j in range(J):
        hv = jax.jvp(grad_y(hb[0][j], hb[1][j]), (y,), (v,))[1]
        v = jnp.where(j < jt, v - hv / L, v)
    v = v * (J / L)
    cross = jax.grad(lambda xx: jnp.sum(
        jax.grad(lower, argnums=1)(xx, y, *gb) * v))(x)
    return gx - cross, jax.grad(lower, argnums=1)(x, y, *gb)


def _node_grads(X, Y, b, keys, *, J, L):
    fa, fb_ = b["f"]
    ga, gb_ = b["g"]
    ha, hb_ = b["h"]
    return jax.vmap(lambda x, y, f1, f2, g1, g2, h1, h2, k: _hypergrad(
        x, y, (f1, f2), (g1, g2), (h1, h2), k, J=J, L=L))(
        X, Y, fa, fb_, ga, gb_, ha, hb_, keys)


def _mix(W, A):
    return jnp.tensordot(W, A, axes=([1], [0]), precision=HI).astype(A.dtype)


def _update(W, x, z, eta, beta):
    return (1.0 - eta) * x + eta * _mix(W, x) - beta * eta * z


@partial(jax.jit, static_argnames=("J", "L", "batch", "hp"))
def init(data, W, X0, Y0, key, *, J, L, batch, hp):
    eta, a1, a2, b1, b2 = hp
    key, k0 = jax.random.split(key)
    kb0, kn0 = jax.random.split(k0)
    b = sample(kb0, data, batch=batch, J=J)
    df, dg = _node_grads(X0, Y0, b, jax.random.split(kn0, X0.shape[0]),
                         J=J, L=L)
    st = {"x": _update(W, X0, df, eta, b1), "y": _update(W, Y0, dg, eta, b2),
          "u": df, "v": dg, "zf": df, "zg": dg}
    return st, key


@partial(jax.jit, static_argnames=("J", "L", "batch", "hp"))
def step(data, W, st, kb, kn, *, J, L, batch, hp):
    eta, a1, a2, b1, b2 = hp
    K = st["x"].shape[0]
    df, dg = _node_grads(st["x"], st["y"], sample(kb, data, batch=batch, J=J),
                         jax.random.split(kn, K), J=J, L=L)
    u = (1.0 - a1 * eta) * st["u"] + a1 * eta * df
    v = (1.0 - a2 * eta) * st["v"] + a2 * eta * dg
    zf = _mix(W, st["zf"]) + u - st["u"]
    zg = _mix(W, st["zg"]) + v - st["v"]
    return {"x": _update(W, st["x"], zf, eta, b1),
            "y": _update(W, st["y"], zg, eta, b2),
            "u": u, "v": v, "zf": zf, "zg": zg}


@jax.jit
def evaluate(st, a, b):
    x, y = jnp.mean(st["x"], 0), jnp.mean(st["y"], 0)
    return {"upper": upper(x, y, a, b), "lower": lower(x, y, a, b)}


def run(data, W, x0, y0, *, seed: int, steps: int, J: int, L: float,
        batch: int, hp: dict, eval_batch, dtype=jnp.float32) -> dict:
    """States and evaluations at t=0 (after the initialising step) and at
    t=steps, from ``jax.random.PRNGKey(seed)`` as the run derives them."""
    cast = lambda t: jax.tree.map(
        lambda v: v.astype(dtype) if jnp.issubdtype(v.dtype, jnp.floating)
        else v, t)
    data, W, eval_batch = cast(data), cast(jnp.asarray(W)), cast(eval_batch)
    K = data[0].shape[0]
    X0 = jnp.broadcast_to(cast(x0)[None], (K,) + x0.shape)
    Y0 = jnp.broadcast_to(cast(y0)[None], (K,) + y0.shape)
    hpt = (hp["eta"], hp["alpha1"], hp["alpha2"], hp["beta1"], hp["beta2"])
    kw = dict(J=J, L=L, batch=batch, hp=hpt)
    key = jax.random.split(jax.random.PRNGKey(seed), 3)[2]  # after kx, ky
    st, key = init(data, W, X0, Y0, key, **kw)
    out = {"t0": st, "eval0": evaluate(st, *eval_batch), "X0": X0, "Y0": Y0}
    for _ in range(steps):
        key, kb, kn = jax.random.split(key, 3)
        st = step(data, W, st, kb, kn, **kw)
    out.update(tE=st, evalE=evaluate(st, *eval_batch))
    return jax.tree.map(np.asarray, out)
