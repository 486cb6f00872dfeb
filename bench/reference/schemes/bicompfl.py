"""Plain reference of BiCompFL rounds (paper Algorithms 1 and 2).

Each round: every client trains its probabilistic mask from its estimate
of the model, conveys ``n_ul`` samples of its posterior by minimal random
coding (MRC) against that estimate, and the federator averages the
conveyed samples into the new model.  The downlink then brings each
client's estimate up to date: GR relays the other clients' indices (every
client decodes the same samples, so all hold the new model); PR conveys
``n_dl`` fresh MRC samples of the new model to each client against its own
estimate, on private randomness.

MRC per block: candidates X_1..X_n_is ~ prior from the shared stream, the
index drawn by Gumbel-max over log Q(X)/P(X).  Fixed allocation cuts the
model into equal blocks (the tail padded with q = p = 1/2).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from common import (TAG_DL_SELECT_PRIVATE, TAG_DL_SHARED, TAG_TRAIN,
                    TAG_UL_SELECT, client_key, clip01, evaluate, gumbel,
                    log_ratio, mask_local_train, round_key, unflatten)

BLOCK_CHUNK = 64  # blocks whose candidates are held at once


def _fixed_sample(skey, sel, q, p, *, size: int, n_is: int):
    """One MRC sample of q against p over fixed blocks of ``size``."""
    d = q.shape[0]
    n_blocks = -(-d // size)
    n_chunks = -(-n_blocks // BLOCK_CHUNK)
    pad = n_chunks * BLOCK_CHUNK * size - d
    half = jnp.full((pad,), 0.5, q.dtype)
    qb = jnp.concatenate([clip01(q), half]).reshape(-1, size)
    pb = jnp.concatenate([clip01(p), half]).reshape(-1, size)
    a, b = log_ratio(qb, pb)

    def chunk(c):
        ids = c * BLOCK_CHUNK + jnp.arange(BLOCK_CHUNK)
        u = jax.vmap(lambda i: jax.random.uniform(
            jax.random.fold_in(skey, i), (n_is, size)))(ids)
        x = u.astype(q.dtype) < pb[ids][:, None, :]
        logw = (jnp.sum(jnp.where(x, a[ids][:, None, :], 0.0), -1)
                + jnp.sum(b[ids], -1)[:, None])
        g = jax.vmap(lambda i: jax.random.uniform(
            jax.random.fold_in(sel, i), (n_is,)))(ids)
        pick = jnp.argmax(logw + gumbel(g.astype(q.dtype)), -1)
        return jnp.take_along_axis(x, pick[:, None, None], 1)[:, 0, :]

    x = jax.lax.map(chunk, jnp.arange(n_chunks))
    return x.reshape(-1)[:d].astype(q.dtype)


def _convey(sample, skey, sel, n_samples: int):
    """Mean of ``n_samples`` MRC samples, candidate and selection keys
    folded with the sample's number."""
    xs = jax.lax.map(lambda ell: sample(jax.random.fold_in(skey, ell),
                                        jax.random.fold_in(sel, ell)),
                     jnp.arange(n_samples))
    return jnp.mean(xs, axis=0)


class Reference:
    """BiCompFL-GR / -PR with fixed blocks, full participation."""

    def __init__(self, cell):
        args = dict(cell.scheme["args"])
        self.variant = args["variant"]
        if self.variant not in ("GR", "PR"):
            raise ValueError(f"reference has no BiCompFL-{self.variant}")
        if args.get("participation", 1.0) != 1.0:
            raise ValueError("reference runs full participation only")
        alloc = args["allocation"]
        if alloc["class"] != "FixedAllocation":
            raise ValueError(f"reference has no {alloc['class']}")
        self.block_size = int(alloc.get("args", {}).get("block_size", 256))
        self.n_is = int(args.get("n_is", 256))
        self.n_ul = int(args.get("n_ul", 1))
        self.n_dl = int(args.get("n_dl", 1))
        self.cell = cell
        self.log2_nis = math.log2(self.n_is)
        t = cell.task
        n = cell.n_clients
        w0, apply, shapes = cell.w0, cell.apply, cell.shapes

        def sample(k, s, q, p):
            return _fixed_sample(k, s, q, p, size=self.block_size,
                                 n_is=self.n_is)

        def local(theta_hat, sx, sy, kt):
            keys = jax.random.split(jax.random.fold_in(kt, TAG_TRAIN), n)
            return jax.vmap(lambda th, x, y, k: mask_local_train(
                th, x, y, k, w0=w0, apply=apply, shapes=shapes,
                epochs=t["local_epochs"], batch=t["batch_size"],
                lr=t["lr"]))(theta_hat, sx, sy, keys)

        def uplink(q, theta_hat, kt):
            sels = jax.vmap(lambda i: jax.random.fold_in(
                jax.random.fold_in(kt, TAG_UL_SELECT), i))(jnp.arange(n))

            def one(i):
                skey = kt if self.variant == "GR" else client_key(kt, i)
                return _convey(lambda k, s: sample(k, s, q[i], theta_hat[i]),
                               skey, sels[i], self.n_ul)

            return jnp.mean(jax.lax.map(one, jnp.arange(n)), axis=0)

        def downlink(theta, theta_hat, kt):
            if self.variant == "GR":
                return jnp.tile(theta[None], (n, 1))

            def one(i):
                skey = jax.random.fold_in(client_key(kt, i), TAG_DL_SHARED)
                sel = jax.random.fold_in(
                    jax.random.fold_in(kt, TAG_DL_SELECT_PRIVATE), i)
                return clip01(_convey(
                    lambda k, s: sample(k, s, theta, theta_hat[i]),
                    skey, sel, self.n_dl))

            return jax.lax.map(one, jnp.arange(n))

        def one_round(theta_hat, sx, sy, kt):
            q = local(theta_hat, sx, sy, kt)
            theta = uplink(q, theta_hat, kt)
            return theta, downlink(theta, theta_hat, kt)

        def acc(theta, xt, yt):
            return evaluate(apply, unflatten(w0 * theta, shapes), xt, yt)[0]

        self._round = jax.jit(one_round)
        self._acc = jax.jit(acc)

    def _bits(self) -> float:
        """Bits booked a round: log2(n_is) per block and conveyed sample."""
        n = self.cell.n_clients
        per = -(-self.cell.d // self.block_size) * self.log2_nis
        up = n * self.n_ul * per
        if self.variant == "GR":
            down = n * (n - 1) * self.n_ul * per
        else:
            down = n * self.n_dl * per
        return up + down

    def run_call(self, theta, seed: int, rounds: int, eval_every: int):
        """One call of ``rounds`` rounds from the model ``theta`` (every
        client's estimate starts at it).  Returns (theta, theta_hat,
        {"bits": booked bits, "acc": [accuracy at each eval round]})."""
        c = self.cell
        theta_hat = jnp.tile(theta[None], (c.n_clients, 1))
        bits, accs = 0.0, []
        for t in range(rounds):
            theta, theta_hat = self._round(theta_hat, c.sx, c.sy,
                                           round_key(seed, t))
            bits += self._bits()
            if (t + 1) % eval_every == 0 or t == rounds - 1:
                accs.append(float(self._acc(theta, c.x_test, c.y_test)))
        return theta, theta_hat, {"bits": bits, "acc": accs}
