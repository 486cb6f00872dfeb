"""Plain reference of FedAvg (McMahan et al. 2017) with a server step.

Each round every client runs Adam from the current model on its shard and
sends its dense delta (32 bits a value); the federator steps the model by
``server_lr`` times the mean delta and broadcasts it densely.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from common import (TAG_TRAIN, dense_local_train, evaluate, round_key,
                    unflatten)

FLOAT_BITS = 32


class Reference:
    def __init__(self, cell):
        args = cell.scheme["args"]
        if args.get("scheme", "fedavg").lower() != "fedavg":
            raise ValueError(f"reference has no {args['scheme']}")
        self.cell = cell
        lr = float(args.get("server_lr", 1.0))
        t = cell.task
        n, apply, shapes = cell.n_clients, cell.apply, cell.shapes

        def one_round(theta, sx, sy, kt):
            keys = jax.random.split(jax.random.fold_in(kt, TAG_TRAIN), n)
            deltas = jax.vmap(lambda x, y, k: dense_local_train(
                theta, x, y, k, apply=apply, shapes=shapes,
                epochs=t["local_epochs"], batch=t["batch_size"],
                lr=t["lr"]))(sx, sy, keys)
            return theta - lr * jnp.mean(deltas, axis=0)

        def acc(theta, xt, yt):
            return evaluate(apply, unflatten(theta, shapes), xt, yt)[0]

        self._round = jax.jit(one_round)
        self._acc = jax.jit(acc)

    def run_call(self, theta, seed: int, rounds: int, eval_every: int):
        c = self.cell
        bits, accs = 0.0, []
        for t in range(rounds):
            theta = self._round(theta, c.sx, c.sy, round_key(seed, t))
            bits += 2 * c.n_clients * c.d * FLOAT_BITS
            if (t + 1) % eval_every == 0 or t == rounds - 1:
                accs.append(float(self._acc(theta, c.x_test, c.y_test)))
        n = c.n_clients
        return theta, jnp.tile(theta[None], (n, 1)), {"bits": bits,
                                                      "acc": accs}
