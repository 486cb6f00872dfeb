"""A benchmark cell, assembled from the data files that name it.

    bench/workloads/<cell>.json   configuration, traffic mix, limits
    bench/configs/<config>.json   net builder and its arguments, data scale
    bench/traffic/<traffic>.json  scheme, deployment, local training, calls

Nothing here knows a cell by name: a new cell is new files.
"""
from __future__ import annotations

import importlib.util
import json
import math
import sys
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict

import numpy as np

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
REF_STEPS = 2  # calls that set-up runs and the reference follows


def load_json(*parts) -> Dict[str, Any]:
    path = BENCH.joinpath(*parts)
    with open(path) as f:
        return json.load(f)


def load_module(*parts):
    """Import a file under bench/ by path (its name may hold dots)."""
    path = BENCH.joinpath(*parts)
    name = "bench_" + "_".join(parts).replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def derived_seed(seed: int, *path: int) -> int:
    """A 31-bit seed derived from the run's seed (any size) and a path."""
    ss = np.random.SeedSequence([seed % 2 ** 64, *path])
    return int(ss.generate_state(1)[0] >> 1)


@dataclass
class Cell:
    name: str
    workload: Dict[str, Any]
    config: Dict[str, Any]
    traffic: Dict[str, Any]

    @classmethod
    def load(cls, name: str, rehearse: bool = False) -> "Cell":
        wl = load_json("workloads", f"{name}.json")
        cfg = load_json("configs", f"{wl['config']}.json")
        tr = load_json("traffic", f"{wl['traffic']}.json")
        if rehearse:  # CPU rehearsal: each file's own tiny sizes
            cfg = _merge(cfg, cfg.get("rehearsal", {}))
            tr = _merge(tr, tr.get("rehearsal", {}))
        return cls(name, wl, cfg, tr)

    # -- sizes ---------------------------------------------------------------

    @property
    def net(self) -> Dict[str, Any]:
        return self.config["net"]

    @property
    def task(self) -> Dict[str, Any]:
        return self.traffic["task"]

    @property
    def scheme(self) -> Dict[str, Any]:
        return self.traffic["scheme"]

    @property
    def n_clients(self) -> int:
        return int(self.traffic["deployment"]["clients"])

    @property
    def rounds_per_call(self) -> int:
        return int(self.traffic["rounds_per_call"])

    @property
    def eval_every(self) -> int:
        return int(self.traffic["eval_every"])

    def net_reference(self):
        return load_module("reference", "nets", f"{self.net['builder']}.py")

    def layer_shapes(self):
        """[(shape, fan_in)] of the net, in parameter order."""
        return self.net_reference().layer_shapes(**self.net["args"])

    @property
    def d(self) -> int:
        return sum(math.prod(s) for s, _ in self.layer_shapes())

    def flops_per_sample(self) -> float:
        """Model FLOPs of one sample's forward pass."""
        mod = load_module("flops", f"{self.net['builder']}.py")
        return float(mod.forward_flops(**self.net["args"]))

    # -- data ----------------------------------------------------------------

    def make_data(self, seed: int):
        """(shard_x, shard_y, test_x, test_y) on the device, from the seed."""
        import jax
        from .datagen import make_data
        dat = self.config["data"]
        dep = self.traffic["deployment"]
        key = jax.random.PRNGKey(derived_seed(seed, 0))
        out = make_data(key, n_train=int(dat["n_train"]),
                        n_test=int(dat["n_test"]),
                        n_classes=int(dat["n_classes"]), hw=int(dat["hw"]),
                        channels=int(dat["channels"]),
                        noise=float(dat["noise"]),
                        n_clients=self.n_clients,
                        per_client=int(dep["per_client"]))
        jax.block_until_ready(out)
        return out

    def net_key(self, seed: int):
        import jax
        return jax.random.PRNGKey(derived_seed(seed, 1))

    def call_seed(self, seed: int, i: int) -> int:
        return derived_seed(seed, 2, i)


def _merge(base: Dict[str, Any], over: Dict[str, Any]) -> Dict[str, Any]:
    out = dict(base)
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = _merge(out[k], v)
        else:
            out[k] = v
    return out


def add_reference_path() -> None:
    """The reference's modules import each other as top-level names."""
    p = str(BENCH / "reference")
    if p not in sys.path:
        sys.path.insert(0, p)
