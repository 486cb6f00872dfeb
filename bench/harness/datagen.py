"""Synthetic federated image data, made on the device from a key.

A copy of the program's generator (``repro.fl.data.make_synthetic`` and
``partition_iid``), kept here so that the benchmark's inputs cannot change
with the program: each class c has a smooth random template T_c (a
low-pass Gaussian field); a sample is T_c + noise * N(0, 1).  Shards are
drawn IID with replacement, equal-sized, one per client.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _smooth_field(key, hw: int, smooth: int = 3):
    raw = jax.random.normal(key, (hw + 2 * smooth, hw + 2 * smooth))
    k = jnp.ones((2 * smooth + 1, 2 * smooth + 1)) / (2 * smooth + 1) ** 2
    sm = jax.scipy.signal.convolve2d(raw, k, mode="valid")
    sm = sm / (jnp.std(sm) + 1e-6)
    return sm[:hw, :hw]


@functools.partial(jax.jit, static_argnames=(
    "n_train", "n_test", "n_classes", "hw", "channels", "noise", "n_clients",
    "per_client"))
def make_data(key, *, n_train: int, n_test: int, n_classes: int, hw: int,
              channels: int, noise: float, n_clients: int, per_client: int):
    """(shard_x (n, per, hw, hw, c), shard_y (n, per), test_x, test_y)."""
    kt, ktr, kte = jax.random.split(key, 3)
    templates = jax.vmap(lambda k: _smooth_field(k, hw))(
        jax.random.split(kt, n_classes * channels))
    templates = templates.reshape(n_classes, channels, hw, hw) \
        .transpose(0, 2, 3, 1)

    def sample(k, n):
        ky, kn = jax.random.split(k)
        y = jax.random.randint(ky, (n,), 0, n_classes)
        x = templates[y] + noise * jax.random.normal(kn, (n, hw, hw, channels))
        return x.astype(jnp.float32), y.astype(jnp.int32)

    x, y = sample(ktr, n_train)
    tx, ty = sample(kte, n_test)
    idx = jax.random.randint(jax.random.fold_in(key, 1),
                             (n_clients, per_client), 0, n_train)
    return x[idx], y[idx], tx, ty
