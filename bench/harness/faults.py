"""Faults planted under the timed path, for the calibration of limits and
for the tests that see ``correct`` come out false.  A run never uses them.

* ``unchanged``: each call returns the state it was given.
* ``half_cohort``: the aggregation averages the first half of the cohort
  and leaves the rest out.
* ``reference_in_place``: the plain reference, at a given precision, put
  in the program's place (bfloat16: the control).
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp


def unchanged(program):
    def call(theta, seed):
        out = program.call(theta, seed)
        n = out["theta_hat"].shape[0]
        return dict(out, theta=theta, theta_hat=jnp.tile(theta[None], (n, 1)))
    return call


class _HalfAggregator:
    def __init__(self, inner):
        self.inner = inner

    def __call__(self, ctx, theta, up_out):
        return self.inner(ctx, theta, up_out[: up_out.shape[0] // 2])


def half_cohort(spec):
    return dataclasses.replace(spec, aggregator=_HalfAggregator(spec.aggregator))


def reference_in_place(dtype):
    def hook(program):
        from . import refrun
        ref, _, _ = refrun.build(program.cell, program.data, program.net_key,
                                 dtype)
        cell = program.cell

        def call(theta, seed):
            th, th_hat, info = ref.run_call(theta.astype(dtype), seed,
                                            cell.rounds_per_call,
                                            cell.eval_every)
            return {"theta": th.astype(jnp.float32),
                    "theta_hat": th_hat.astype(jnp.float32),
                    "meter": {"total_bits": info["bits"]},
                    "history": [{"acc": a} for a in info["acc"]],
                    "mode": "fused"}
        return call
    return hook
