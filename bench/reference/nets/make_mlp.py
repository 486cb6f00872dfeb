"""Plain reference of the bias-free MLP: ReLU dense layers and a linear
head.  Arguments are those of the program's ``make_mlp`` builder."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def layer_shapes(in_dim, widths=(256, 256), n_classes=10):
    dims = [in_dim, *widths, n_classes]
    return [((a, b), a) for a, b in zip(dims[:-1], dims[1:])]


def make_apply(in_dim, widths=(256, 256), n_classes=10,
               precision=jax.lax.Precision.HIGHEST):
    def apply(weights, x):
        h = x.reshape(x.shape[0], -1)
        for w in weights[:-1]:
            h = jax.nn.relu(jnp.dot(h, w, precision=precision))
        return jnp.dot(h, weights[-1], precision=precision)

    return apply


def init(key, shapes, signed_constant: bool):
    keys = jax.random.split(key, len(shapes))
    out = []
    for k, (s, fan_in) in zip(keys, shapes):
        std = math.sqrt(2.0 / fan_in)
        n = jax.random.normal(k, s)
        out.append(jnp.sign(n) * std if signed_constant else n * std)
    return out
