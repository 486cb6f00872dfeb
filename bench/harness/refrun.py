"""Run the plain reference of a cell: the same calls from the same seed.

The reference gets the harness's data and the key of the program's weight
init, and builds its weights itself.  Its matrix products run at the
configuration's ``matmul_precision``; the test loss that the comparison
reads is taken at HIGHEST.  ``dtype=bfloat16`` runs the same reference one
precision below the configuration's float32: the control.
"""
from __future__ import annotations

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from .cell import REF_STEPS, add_reference_path, load_module
from .correct import Record, Step

PRECISION = {"default": jax.lax.Precision.DEFAULT,
             "high": jax.lax.Precision.HIGH,
             "highest": jax.lax.Precision.HIGHEST}


def build(cell, data, net_key, dtype=jnp.float32):
    """(scheme reference, its context, theta0)."""
    add_reference_path()
    common = load_module("reference", "common.py")
    netmod = cell.net_reference()
    shapes_fan = netmod.layer_shapes(**cell.net["args"])
    shapes = [s for s, _ in shapes_fan]
    t = cell.task
    signed = bool(t.get("net_args", {}).get("signed_constant", False))
    w = netmod.init(net_key, shapes_fan, signed)
    w0 = jnp.concatenate([x.reshape(-1) for x in w]).astype(dtype)
    sx, sy, tx, ty = data
    precision = PRECISION[cell.config["matmul_precision"]]
    ctx = SimpleNamespace(
        n_clients=cell.n_clients, d=cell.d, sx=sx.astype(dtype), sy=sy,
        x_test=tx.astype(dtype), y_test=ty, w0=w0, shapes=shapes,
        apply=netmod.make_apply(**cell.net["args"], precision=precision),
        task=t, scheme=cell.scheme)
    apply_highest = netmod.make_apply(**cell.net["args"])
    if t["kind"] == "mask":
        theta0 = jnp.full((cell.d,), float(t["theta_init"]), dtype)
    else:
        theta0 = w0
    scheme = load_module("reference", "schemes",
                         f"{cell.traffic['reference']}.py")

    def loss(theta):
        th = theta.astype(jnp.float32)
        weights = th * w0.astype(jnp.float32) if t["kind"] == "mask" else th
        return common.evaluate(apply_highest,
                               common.unflatten(weights, shapes), tx, ty)[1]

    ctx.loss = jax.jit(loss)
    ctx.slices = common.leaf_slices(shapes)
    return scheme.Reference(ctx), ctx, theta0


def run(cell, data, net_key, seed: int, dtype=jnp.float32) -> Record:
    """The reference's ``REF_STEPS`` calls, each from the last's model."""
    ref, ctx, theta = build(cell, data, net_key, dtype)
    rec = Record(theta0=np.asarray(theta, np.float32),
                 loss0=float(ctx.loss(theta)))
    for k in range(REF_STEPS):
        theta, theta_hat, info = ref.run_call(
            theta, cell.call_seed(seed, k), cell.rounds_per_call,
            cell.eval_every)
        rec.steps.append(Step(
            theta=np.asarray(theta.astype(jnp.float32)),
            theta_hat=np.asarray(theta_hat.astype(jnp.float32)),
            bits=info["bits"], acc=info["acc"],
            loss=float(ctx.loss(theta))))
    return rec


def loss_fn(cell, data, net_key):
    """The reference's test loss, for evaluating the program's models."""
    _, ctx, _ = build(cell, data, net_key)
    return lambda theta: float(ctx.loss(jnp.asarray(theta))), ctx.slices
