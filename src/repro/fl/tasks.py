"""Local-training tasks: the client-side optimization step of the FL loop.

* ``MaskTask``: FedPM-style probabilistic mask training (paper Appendix G).
  The model is a vector theta in [0,1]^d of Bernoulli parameters over a
  *fixed* randomly-initialized network w.  Local training is mirror descent:
  map theta to dual scores s = sigma^{-1}(theta), take L SGD passes on s with
  the straight-through estimator through the Bernoulli sampling, map back.
  The KL-proximity geometry of this update is exactly what makes the MRC
  uplink cheap (communication cost ~ d_KL(q || theta_hat)).

* ``CFLTask``: conventional FL.  Local training runs L epochs of Adam/SGD
  from the client's model estimate and returns the model *delta* (the
  "gradient" that the compressors quantize).

Both tasks are pytrees: their frozen arrays (the fixed weights, the test
set) are leaves, the net and the hyperparameters static.  Every jitted
method takes ``self`` as an argument, so a program that calls the task --
the engine's fused round scan, the host loop's jits -- receives the arrays
as arguments and compiles none of them in as a constant.  A new seed (new
weights, new data) then reuses the compiled program.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Callable, Tuple

import jax
import jax.numpy as jnp

from repro import optim
from repro.core.bernoulli import clip01, inv_sigmoid
from .nets import Net, accuracy, cross_entropy, flatten_weights


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["w0_flat", "x_test", "y_test"],
                   meta_fields=["net", "unravel", "local_epochs", "batch_size",
                                "lr", "optimizer", "theta_init"])
@dataclass(eq=False)
class MaskTask:
    net: Net
    w0_flat: jax.Array          # fixed signed-constant weights, flattened
    unravel: Callable
    x_test: jax.Array
    y_test: jax.Array
    local_epochs: int = 3
    batch_size: int = 128
    lr: float = 0.1   # paper: Adam in score space with lr 0.1
    optimizer: str = "adam"  # adam | sgd -- Adam is essential: averaged
                             # binary masks saturate theta at {0, 1} where
                             # sigmoid gradients vanish; Adam renormalizes
    theta_init: float = 0.5

    @property
    def d(self) -> int:
        return int(self.w0_flat.shape[0])

    def init_theta(self) -> jax.Array:
        return jnp.full((self.d,), self.theta_init, jnp.float32)

    # -- client step ------------------------------------------------------
    def ste_loss(self, s: jax.Array, xb: jax.Array, yb: jax.Array,
                 mk: jax.Array) -> jax.Array:
        """Batch cross-entropy under a mask drawn from sigmoid(s); its
        gradient in s is the straight-through estimator's."""
        prob = jax.nn.sigmoid(s)
        m = jax.random.bernoulli(mk, prob).astype(jnp.float32)
        m_ste = m + prob - jax.lax.stop_gradient(prob)  # straight-through
        weights = self.unravel(self.w0_flat * m_ste)
        return cross_entropy(self.net.apply(weights, xb), yb)

    @jax.jit
    def local_train(self, theta: jax.Array, xs: jax.Array, ys: jax.Array,
                    key: jax.Array):
        """L epochs of score-space SGD with STE; returns the posterior q."""
        shard = xs.shape[0]
        bs = min(self.batch_size, shard)
        steps_per_epoch = max(shard // bs, 1)
        n_steps = self.local_epochs * steps_per_epoch
        kb, km = jax.random.split(key)
        batch_idx = jax.random.randint(kb, (n_steps, bs), 0, shard)
        opt = optim.adam(self.lr) if self.optimizer == "adam" else optim.sgd(self.lr)

        def step(carry, inp):
            s, st = carry
            idx, mk = inp
            with jax.named_scope("local.batch"):
                xb, yb = xs[idx], ys[idx]
            g = jax.grad(self.ste_loss)(s, xb, yb, mk)
            s, st = opt.update(g, s, st)
            return (s, st), ()

        s0 = inv_sigmoid(theta)
        mks = jax.random.split(km, n_steps)
        (s_fin, _), _ = jax.lax.scan(step, (s0, opt.init(s0)), (batch_idx, mks))
        return clip01(jax.nn.sigmoid(s_fin))

    # -- evaluation -------------------------------------------------------
    @jax.jit
    def evaluate(self, theta: jax.Array) -> jax.Array:
        """Accuracy with the expected mask (w * theta) -- low-variance eval."""
        weights = self.unravel(self.w0_flat * theta)
        return accuracy(self.net.apply, weights, self.x_test, self.y_test)


def make_mask_task(net: Net, key: jax.Array, x_test, y_test, **kw) -> MaskTask:
    w0 = net.init(key)
    w0_flat, unravel = flatten_weights(w0)
    return MaskTask(net=net, w0_flat=w0_flat, unravel=unravel,
                    x_test=x_test, y_test=y_test, **kw)


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["x_test", "y_test"],
                   meta_fields=["net", "unravel", "d", "local_epochs",
                                "batch_size", "local_lr", "optimizer"])
@dataclass(eq=False)
class CFLTask:
    net: Net
    unravel: Callable
    d: int
    x_test: jax.Array
    y_test: jax.Array
    local_epochs: int = 3
    batch_size: int = 128
    local_lr: float = 3e-4
    optimizer: str = "adam"

    @jax.jit
    def local_train(self, theta: jax.Array, xs: jax.Array, ys: jax.Array,
                    key: jax.Array):
        """Return the local model delta ("gradient") after L epochs."""
        shard = xs.shape[0]
        bs = min(self.batch_size, shard)
        steps_per_epoch = max(shard // bs, 1)
        n_steps = self.local_epochs * steps_per_epoch
        batch_idx = jax.random.randint(key, (n_steps, bs), 0, shard)

        opt = optim.adam(self.local_lr) if self.optimizer == "adam" else optim.sgd(self.local_lr)

        def loss_fn(w, xb, yb):
            return cross_entropy(self.net.apply(self.unravel(w), xb), yb)

        def step(carry, idx):
            w, st = carry
            with jax.named_scope("local.batch"):
                xb, yb = xs[idx], ys[idx]
            g = jax.grad(loss_fn)(w, xb, yb)
            w, st = opt.update(g, w, st)
            return (w, st), ()

        (w_fin, _), _ = jax.lax.scan(step, (theta, opt.init(theta)), batch_idx)
        return theta - w_fin  # "gradient" = negative update direction

    @jax.jit
    def evaluate(self, theta: jax.Array) -> jax.Array:
        return accuracy(self.net.apply, self.unravel(theta), self.x_test,
                        self.y_test)


def make_cfl_task(net: Net, key: jax.Array, x_test, y_test, **kw) -> Tuple[CFLTask, jax.Array]:
    w0 = net.init(key)
    w0_flat, unravel = flatten_weights(w0)
    task = CFLTask(net=net, unravel=unravel, d=int(w0_flat.shape[0]),
                   x_test=x_test, y_test=y_test, **kw)
    return task, w0_flat
