"""The system under test: the repro FL engine, built from a cell's files.

The harness hands the program only data it made itself (the shards and the
test set) and a key for the program's own weight init; everything else is
the program's normal path: ``repro.fl.registry`` builds the scheme,
``repro.fl.tasks`` the local training, ``FLEngine.run(mode="fused")`` runs
the rounds.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp


def _resolve(v, env: Dict[str, Any]):
    """Scheme arguments as data: "$name" takes a value of the run, and
    {"class": C, "args": {...}} builds ``repro.core.blocks.C``."""
    from repro.core import blocks
    if isinstance(v, str) and v.startswith("$"):
        return env[v[1:]]
    if isinstance(v, dict) and "class" in v:
        return getattr(blocks, v["class"])(**_resolve(v.get("args", {}), env))
    if isinstance(v, dict):
        return {k: _resolve(x, env) for k, x in v.items()}
    if isinstance(v, list):
        return [_resolve(x, env) for x in v]
    return v


class Program:
    """One engine and its inputs; ``call`` runs one call of the window."""

    def __init__(self, cell, data, net_key, *,
                 spec_transform: Optional[Callable] = None):
        from repro.fl import nets, registry, tasks
        from repro.fl.data import Dataset
        from repro.fl.engine import FLEngine

        sx, sy, tx, ty = data
        self.cell, self.data, self.net_key = cell, data, net_key
        self.shards = Dataset(x=sx, y=sy)
        t = cell.task
        net = getattr(nets, cell.net["builder"])(
            **cell.net["args"], **t.get("net_args", {}))
        kw = dict(local_epochs=int(t["local_epochs"]),
                  batch_size=int(t["batch_size"]), optimizer=t["optimizer"])
        if t["kind"] == "mask":
            self.task = tasks.make_mask_task(net, net_key, tx, ty,
                                             lr=float(t["lr"]), **kw)
            self.theta0 = jnp.full((self.task.d,), float(t["theta_init"]),
                                   jnp.float32)
        elif t["kind"] == "dense":
            self.task, self.theta0 = tasks.make_cfl_task(
                net, net_key, tx, ty, local_lr=float(t["lr"]), **kw)
        else:
            raise ValueError(f"task kind {t['kind']!r}")
        d = int(self.theta0.shape[0])
        if d != cell.d:
            raise ValueError(f"program's d={d}, configuration's d={cell.d}")
        s = cell.scheme
        env = {"clients": cell.n_clients, "d": d}
        spec = getattr(registry, s["fn"])(**_resolve(s["args"], env))
        if spec_transform is not None:
            spec = spec_transform(spec)
        self.engine = FLEngine(self.task, spec)
        self.rounds = cell.rounds_per_call
        self.eval_every = cell.eval_every

    def call(self, theta, seed: int) -> Dict[str, Any]:
        """One fused run of ``rounds_per_call`` rounds from ``theta``,
        finished on the device."""
        with jax.profiler.TraceAnnotation("bench.engine_run"):
            out = self.engine.run(self.shards, theta, rounds=self.rounds,
                                  seed=seed, eval_every=self.eval_every,
                                  mode="fused")
        with jax.profiler.TraceAnnotation("bench.wait"):
            jax.block_until_ready((out["theta"], out["theta_hat"]))
        if out["mode"] != "fused":
            raise RuntimeError(f"the call ran mode={out['mode']!r}, "
                               "not fused")
        return out
