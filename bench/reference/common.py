"""Plain building blocks of the reference FL rounds.

The reference imports nothing of the program under test and takes nothing
that it made.  It rebuilds every random stream of a round from the run's
seed with ``jax.random``, as the BiCompFL protocol defines them (round key
``fold_in(PRNGKey(seed), t)``, per-client and per-direction keys by fixed
tags), and computes the rest in plain ``jax.numpy``.  Matrix products run
at the precision that the configuration states.  ``dtype`` sets the type
of every array the reference holds: float32 is the reference, bfloat16 is
the benchmark's control (the same code one precision lower).
"""
from __future__ import annotations

import math
from typing import Callable, List, Sequence, Tuple

import jax
import jax.numpy as jnp

EPS = 1e-6               # Bernoulli parameters live in [EPS, 1 - EPS]
TAG_TRAIN = 1            # per-round local-training keys
TAG_UL_SELECT = 2        # uplink selection stream
TAG_DL_SHARED = 3        # private downlink candidate stream
TAG_DL_SELECT_PRIVATE = 5  # private downlink selection stream
CLIENT_TAG = 0x5EED      # per-client private shared randomness


def clip01(x):
    return jnp.clip(x, EPS, 1.0 - EPS)


def round_key(seed: int, t):
    return jax.random.fold_in(jax.random.PRNGKey(seed), t)


def client_key(kt, i):
    return jax.random.fold_in(jax.random.fold_in(kt, CLIENT_TAG), i)


def log_ratio(q, p):
    """(a, b) with log Q(x)/P(x) = sum_e x_e a_e + b_e for x in {0,1}^d."""
    q, p = clip01(q), clip01(p)
    llr1 = jnp.log(q) - jnp.log(p)
    llr0 = jnp.log1p(-q) - jnp.log1p(-p)
    return llr1 - llr0, llr0


def gumbel(u):
    return -jnp.log(-jnp.log(jnp.clip(u, 1e-12, 1.0 - 1e-12)))


def cross_entropy(logits, y):
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))


# -- flat parameter vectors --------------------------------------------------


def unflatten(flat, shapes: Sequence[Tuple[int, ...]]) -> List[jax.Array]:
    out, off = [], 0
    for s in shapes:
        n = math.prod(s)
        out.append(flat[off:off + n].reshape(s))
        off += n
    return out


def leaf_slices(shapes) -> List[slice]:
    out, off = [], 0
    for s in shapes:
        n = math.prod(s)
        out.append(slice(off, off + n))
        off += n
    return out


# -- Adam, as in Kingma & Ba (2015), bias-corrected --------------------------


def adam_init(p):
    return jnp.zeros_like(p), jnp.zeros_like(p), jnp.zeros((), jnp.int32)


def adam_update(g, p, state, lr, b1=0.9, b2=0.999, eps=1e-8):
    mu, nu, step = state
    step = step + 1
    mu = b1 * mu + (1 - b1) * g
    nu = b2 * nu + (1 - b2) * g * g
    t = step.astype(jnp.float32)
    bc1 = (1 - b1 ** t).astype(p.dtype)
    bc2 = (1 - b2 ** t).astype(p.dtype)
    return p - lr * (mu / bc1) / (jnp.sqrt(nu / bc2) + eps), (mu, nu, step)


# -- local training ----------------------------------------------------------


def _batches(key_idx, shard: int, epochs: int, batch: int):
    bs = min(batch, shard)
    n_steps = epochs * max(shard // bs, 1)
    return jax.random.randint(key_idx, (n_steps, bs), 0, shard), n_steps


def mask_local_train(theta, xs, ys, key, *, w0, apply: Callable, shapes,
                     epochs: int, batch: int, lr: float):
    """FedPM local training: Adam on the scores s = logit(theta), with a
    sampled Bernoulli mask and the straight-through estimator.  Returns
    the client's posterior q = clip(sigmoid(s))."""
    kb, km = jax.random.split(key)
    idx, n_steps = _batches(kb, xs.shape[0], epochs, batch)
    mks = jax.random.split(km, n_steps)

    def loss(s, xb, yb, mk):
        prob = jax.nn.sigmoid(s)
        m = jax.random.bernoulli(mk, prob).astype(s.dtype)
        m_ste = m + prob - jax.lax.stop_gradient(prob)
        return cross_entropy(apply(unflatten(w0 * m_ste, shapes), xb), yb)

    def step(carry, inp):
        s, st = carry
        i, mk = inp
        g = jax.grad(loss)(s, xs[i], ys[i], mk)
        return adam_update(g, s, st, lr), ()

    th = clip01(theta)
    s0 = jnp.log(th) - jnp.log1p(-th)
    (s, _), _ = jax.lax.scan(step, (s0, adam_init(s0)), (idx, mks))
    return clip01(jax.nn.sigmoid(s))


def dense_local_train(theta, xs, ys, key, *, apply: Callable, shapes,
                      epochs: int, batch: int, lr: float):
    """Conventional FL local training: Adam on the weights; returns the
    model delta theta - w_final."""
    idx, _ = _batches(key, xs.shape[0], epochs, batch)

    def loss(w, xb, yb):
        return cross_entropy(apply(unflatten(w, shapes), xb), yb)

    def step(carry, i):
        w, st = carry
        g = jax.grad(loss)(w, xs[i], ys[i])
        return adam_update(g, w, st, lr), ()

    (w, _), _ = jax.lax.scan(step, (theta, adam_init(theta)), idx)
    return theta - w


# -- evaluation --------------------------------------------------------------


def evaluate(apply: Callable, weights, x, y, block: int = 1000):
    """(accuracy, mean cross-entropy) over the test set, in row blocks."""
    n = x.shape[0]
    nb = -(-n // block)
    pad = nb * block - n
    xp = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
    yp = jnp.pad(y, (0, pad))
    valid = jnp.arange(nb * block) < n

    def one(i):
        xi = jax.lax.dynamic_slice_in_dim(xp, i * block, block)
        yi = jax.lax.dynamic_slice_in_dim(yp, i * block, block)
        vi = jax.lax.dynamic_slice_in_dim(valid, i * block, block)
        logits = apply(weights, xi).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits)
        nll = -jnp.take_along_axis(logp, yi[:, None], axis=1)[:, 0]
        hit = jnp.argmax(logits, -1) == yi
        return (jnp.sum(jnp.where(vi, hit, False).astype(jnp.float32)),
                jnp.sum(jnp.where(vi, nll, 0.0)))

    hits, nll = jax.lax.map(one, jnp.arange(nb))
    return jnp.sum(hits) / n, jnp.sum(nll) / n
