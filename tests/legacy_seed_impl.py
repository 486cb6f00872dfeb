"""Verbatim copy of the seed's monolithic FL loops, kept ONLY as the parity
oracle for tests/test_engine_parity.py.

The production code now routes everything through the composable
Channel/Engine API (repro.fl.channels / repro.fl.engine); these functions
preserve the exact pre-refactor semantics -- per-client Python loops, full
local training under partial participation, inline bit formulas -- so the
tests can assert the new engine reproduces the old histories bit-for-bit.
Do not "fix" or modernise this file.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import mrc
from repro.core.bernoulli import bern_kl, clip01
from repro.core.bitmeter import BitMeter
from repro.core.blocks import AdaptiveAllocation, FixedAllocation
from repro.core.quantizers import FLOAT_BITS, sign_compress, topk_bits, topk_compress


# The seed loops' run configurations.  tests/test_engine_parity.py builds
# the engine's spec from the same config through repro.fl.registry.


@dataclass
class BiCompFLConfig:
    variant: str = "GR"          # GR | GR-Reconst | PR | PR-SplitDL
    allocation: Any = field(default_factory=lambda: FixedAllocation(256))
    n_is: int = 256
    n_ul: int = 1
    n_dl: Optional[int] = None   # default: n_clients * n_ul (paper)
    rounds: int = 30
    seed: int = 0
    eval_every: int = 1
    chunk: int = 16              # MRC encode block-chunk (memory knob)
    logw_fn: Any = None          # optionally the Pallas kernel closure
    participation: float = 1.0   # fraction of clients per round; < 1 only
                                 # valid for PR variants


@dataclass
class CFLConfig:
    n_is: int = 256
    n_ul: int = 1
    block_size: int = 16
    rounds: int = 30
    server_lr: float = 1.0
    seed: int = 0
    eval_every: int = 1
    chunk: int = 16
    temperature: str = "auto"    # K: "auto" => mean |delta| per client
    logw_fn: Any = None


@dataclass
class BaselineConfig:
    scheme: str = "fedavg"
    rounds: int = 30
    server_lr: float = 1.0
    seed: int = 0
    eval_every: int = 1
    reset_period: int = 50   # CSER / LIEC periodic sync


def to_blocks(v: jax.Array, size: int) -> jax.Array:
    d = v.shape[-1]
    b = -(-d // size)
    pad = b * size - d
    if pad:
        v = jnp.concatenate([v, jnp.full(v.shape[:-1] + (pad,), 0.5, v.dtype)], axis=-1)
    return v.reshape(v.shape[:-1] + (b, size))


def from_blocks(m: jax.Array, d: int) -> jax.Array:
    return m.reshape(m.shape[:-2] + (-1,))[..., :d]


def _uplink_bits(n_clients, n_ul, n_blocks, n_is):
    return n_clients * n_ul * n_blocks * math.log2(n_is)


def run_bicompfl_legacy(task, shards, cfg: BiCompFLConfig) -> Dict[str, Any]:
    n = int(shards.x.shape[0])
    d = task.d
    n_dl = cfg.n_dl if cfg.n_dl is not None else n * cfg.n_ul
    base = jax.random.PRNGKey(cfg.seed)
    is_gr = cfg.variant.startswith("GR")
    meter = BitMeter(n_clients=n, d=d, broadcast_downlink_shareable=is_gr)

    theta_hat = jnp.tile(task.init_theta()[None], (n, 1))
    history: List[Dict[str, float]] = []
    adaptive = isinstance(cfg.allocation, AdaptiveAllocation)

    if cfg.participation < 1.0 and cfg.variant != "PR":
        raise ValueError("partial participation requires PR")
    n_active = max(1, int(round(cfg.participation * n)))
    rng = np.random.default_rng(cfg.seed + 17)

    log2_nis = math.log2(cfg.n_is)
    for t in range(cfg.rounds):
        kt = mrc.round_key(base, t)
        active = sorted(rng.choice(n, size=n_active, replace=False)) \
            if n_active < n else list(range(n))
        train_keys = jax.random.split(jax.random.fold_in(kt, 1), n)

        q = jax.vmap(task.local_train)(theta_hat, shards.x, shards.y, train_keys)
        q = clip01(q)

        kl_mean = np.asarray(jnp.mean(jax.vmap(bern_kl)(q, clip01(theta_hat)), axis=0))
        size, n_blocks, seg_ids, overhead = cfg.allocation.plan(kl_mean, d)

        def up_one(i, q_i, p_i):
            skey = kt if is_gr else mrc.client_key(kt, i)
            sel = jax.random.fold_in(jax.random.fold_in(kt, 2), i)
            if adaptive:
                idxs, q_hat = mrc.transmit_segments(
                    skey, sel, q_i, clip01(p_i), jnp.asarray(seg_ids),
                    n_is=cfg.n_is, n_seg=n_blocks, n_samples=cfg.n_ul)
                return idxs, q_hat
            qb, pb = to_blocks(q_i, size), to_blocks(clip01(p_i), size)
            idxs, q_hat_b = mrc.transmit_fixed(
                skey, sel, qb, pb, n_is=cfg.n_is, n_samples=cfg.n_ul,
                chunk=cfg.chunk, logw_fn=cfg.logw_fn)
            return idxs, from_blocks(q_hat_b, d)

        q_hats = []
        for i in active:
            _, q_hat_i = up_one(i, q[i], theta_hat[i])
            q_hats.append(q_hat_i)
        q_hat = jnp.stack(q_hats)
        theta_next = jnp.mean(q_hat, axis=0)

        ul_bits = _uplink_bits(len(active), cfg.n_ul, n_blocks, cfg.n_is)

        if cfg.variant == "GR":
            theta_hat = jnp.tile(theta_next[None], (n, 1))
            dl_bits = n * (n - 1) * cfg.n_ul * n_blocks * log2_nis
        elif cfg.variant == "GR-Reconst":
            skey = jax.random.fold_in(kt, 3)
            sel = jax.random.fold_in(kt, 4)
            p_common = clip01(theta_hat[0])
            if adaptive:
                _, est = mrc.transmit_segments(
                    skey, sel, theta_next, p_common, jnp.asarray(seg_ids),
                    n_is=cfg.n_is, n_seg=n_blocks, n_samples=n_dl)
            else:
                _, est_b = mrc.transmit_fixed(
                    skey, sel, to_blocks(theta_next, size), to_blocks(p_common, size),
                    n_is=cfg.n_is, n_samples=n_dl, chunk=cfg.chunk, logw_fn=cfg.logw_fn)
                est = from_blocks(est_b, d)
            theta_hat = jnp.tile(clip01(est)[None], (n, 1))
            dl_bits = n * n_dl * n_blocks * log2_nis
        elif cfg.variant == "PR":
            new_hats = list(theta_hat)
            for i in active:
                skey = jax.random.fold_in(mrc.client_key(kt, i), 3)
                sel = jax.random.fold_in(jax.random.fold_in(kt, 5), i)
                if adaptive:
                    _, est = mrc.transmit_segments(
                        skey, sel, theta_next, clip01(theta_hat[i]), jnp.asarray(seg_ids),
                        n_is=cfg.n_is, n_seg=n_blocks, n_samples=n_dl)
                else:
                    _, est_b = mrc.transmit_fixed(
                        skey, sel, to_blocks(theta_next, size),
                        to_blocks(clip01(theta_hat[i]), size),
                        n_is=cfg.n_is, n_samples=n_dl, chunk=cfg.chunk, logw_fn=cfg.logw_fn)
                    est = from_blocks(est_b, d)
                new_hats[i] = clip01(est)
            theta_hat = jnp.stack(new_hats)
            dl_bits = len(active) * n_dl * n_blocks * log2_nis
        elif cfg.variant == "PR-SplitDL":
            if adaptive:
                raise NotImplementedError("SplitDL is defined on fixed blocks")
            tb = to_blocks(theta_next, size)
            new_hats = []
            blocks_per_client = 0
            for i in range(n):
                own = np.arange(i, n_blocks, n)
                blocks_per_client = max(blocks_per_client, len(own))
                skey = jax.random.fold_in(mrc.client_key(kt, i), 3)
                sel = jax.random.fold_in(jax.random.fold_in(kt, 5), i)
                hb = to_blocks(clip01(theta_hat[i]), size)
                _, est_b = mrc.transmit_fixed(
                    skey, sel, tb[own], hb[own], n_is=cfg.n_is, n_samples=n_dl,
                    chunk=min(cfg.chunk, max(len(own), 1)), logw_fn=cfg.logw_fn)
                hb = hb.at[own].set(clip01(est_b))
                new_hats.append(from_blocks(hb, d))
            theta_hat = jnp.stack(new_hats)
            dl_bits = n * n_dl * blocks_per_client * log2_nis
        else:
            raise ValueError(cfg.variant)

        meter.add_round(ul_bits, dl_bits, overhead_bits=overhead * n)

        if (t + 1) % cfg.eval_every == 0 or t == cfg.rounds - 1:
            acc = task.evaluate(theta_next)
            history.append({"round": t + 1, "acc": float(acc),
                            "cum_bits": meter.total_bits,
                            "bpp_so_far": meter.total_bpp})

    return {"history": history, "meter": meter.summary(),
            "theta": theta_next, "theta_hat": theta_hat,
            "final_acc": history[-1]["acc"] if history else float("nan"),
            "max_acc": max(h["acc"] for h in history) if history else float("nan")}


def run_bicompfl_cfl_legacy(task, theta0, shards, cfg: CFLConfig) -> Dict[str, Any]:
    n = int(shards.x.shape[0])
    d = int(theta0.shape[0])
    base = jax.random.PRNGKey(cfg.seed)
    meter = BitMeter(n_clients=n, d=d, broadcast_downlink_shareable=True)
    theta = theta0
    n_blocks = -(-d // cfg.block_size)
    log2_nis = math.log2(cfg.n_is)
    history: List[Dict[str, float]] = []

    p_blocks = jnp.full((n_blocks, cfg.block_size), 0.5, jnp.float32)

    for t in range(cfg.rounds):
        kt = mrc.round_key(base, t)
        train_keys = jax.random.split(jax.random.fold_in(kt, 1), n)
        deltas = jax.vmap(task.local_train)(
            jnp.tile(theta[None], (n, 1)), shards.x, shards.y, train_keys)

        g_hats = []
        for i in range(n):
            delta = deltas[i]
            K = jnp.mean(jnp.abs(delta)) + 1e-12
            q_i = clip01(jax.nn.sigmoid(delta / K))
            sel = jax.random.fold_in(jax.random.fold_in(kt, 2), i)
            _, q_hat_b = mrc.transmit_fixed(
                kt, sel, to_blocks(q_i, cfg.block_size), p_blocks,
                n_is=cfg.n_is, n_samples=cfg.n_ul, chunk=cfg.chunk, logw_fn=cfg.logw_fn)
            q_hat = from_blocks(q_hat_b, d)
            g_hats.append((2.0 * q_hat - 1.0) * K)
        g_hat = jnp.mean(jnp.stack(g_hats), axis=0)
        theta = theta - cfg.server_lr * g_hat

        ul = _uplink_bits(n, cfg.n_ul, n_blocks, cfg.n_is) + 32 * n
        dl = n * (n - 1) * cfg.n_ul * n_blocks * log2_nis + 32 * n * (n - 1)
        meter.add_round(ul, dl)

        if (t + 1) % cfg.eval_every == 0 or t == cfg.rounds - 1:
            acc = task.evaluate(theta)
            history.append({"round": t + 1, "acc": float(acc),
                            "cum_bits": meter.total_bits})

    return {"history": history, "meter": meter.summary(), "theta": theta,
            "final_acc": history[-1]["acc"] if history else float("nan"),
            "max_acc": max(h["acc"] for h in history) if history else float("nan")}


def run_baseline_legacy(task, theta0, shards, cfg: BaselineConfig) -> Dict[str, Any]:
    n = int(shards.x.shape[0])
    d = int(theta0.shape[0])
    base = jax.random.PRNGKey(cfg.seed)
    scheme = cfg.scheme.lower()
    meter = BitMeter(n_clients=n, d=d,
                     broadcast_downlink_shareable=(scheme != "m3"))

    theta = theta0
    theta_hat = jnp.tile(theta0[None], (n, 1))
    e_up = jnp.zeros((n, d))
    e_down = jnp.zeros((d,))
    k_m3 = max(d // n, 1)
    history: List[Dict[str, float]] = []

    def sign2(v):
        c1 = sign_compress(v)
        c2 = sign_compress(v - c1)
        return c1 + c2

    for t in range(cfg.rounds):
        kt = jax.random.fold_in(base, t)
        train_keys = jax.random.split(jax.random.fold_in(kt, 1), n)
        deltas = jax.vmap(task.local_train)(theta_hat, shards.x, shards.y, train_keys)

        ul_bits = dl_bits = 0.0
        if scheme == "fedavg":
            agg = jnp.mean(deltas, axis=0)
            theta = theta - cfg.server_lr * agg
            theta_hat = jnp.tile(theta[None], (n, 1))
            ul_bits = n * d * FLOAT_BITS
            dl_bits = n * d * FLOAT_BITS
        elif scheme in ("memsgd", "cser"):
            c = jax.vmap(sign_compress)(deltas + e_up)
            e_up = deltas + e_up - c
            theta = theta - cfg.server_lr * jnp.mean(c, axis=0)
            theta_hat = jnp.tile(theta[None], (n, 1))
            ul_bits = n * (d + FLOAT_BITS)
            dl_bits = n * d * FLOAT_BITS
            if scheme == "cser" and (t + 1) % cfg.reset_period == 0:
                theta = theta - cfg.server_lr * jnp.mean(e_up, axis=0)
                e_up = jnp.zeros_like(e_up)
                theta_hat = jnp.tile(theta[None], (n, 1))
                ul_bits += n * d * FLOAT_BITS
                dl_bits += n * d * FLOAT_BITS
        elif scheme in ("doublesqueeze", "neolithic", "liec"):
            comp = sign2 if scheme == "neolithic" else sign_compress
            bits_per = 2.0 if scheme == "neolithic" else 1.0
            c = jax.vmap(comp)(deltas + e_up)
            e_up = deltas + e_up - c
            agg = jnp.mean(c, axis=0) + e_down
            c_s = comp(agg)
            e_down = agg - c_s
            theta = theta - cfg.server_lr * c_s
            theta_hat = theta_hat - cfg.server_lr * c_s[None, :]
            ul_bits = n * (bits_per * d + FLOAT_BITS * (2 if scheme == "neolithic" else 1))
            dl_bits = n * (bits_per * d + FLOAT_BITS * (2 if scheme == "neolithic" else 1))
            if scheme == "liec" and (t + 1) % cfg.reset_period == 0:
                theta = theta - cfg.server_lr * (jnp.mean(e_up, axis=0) + e_down)
                e_up = jnp.zeros_like(e_up)
                e_down = jnp.zeros_like(e_down)
                theta_hat = jnp.tile(theta[None], (n, 1))
                ul_bits += n * d * FLOAT_BITS
                dl_bits += n * d * FLOAT_BITS
        elif scheme == "m3":
            c = jax.vmap(lambda v: topk_compress(v, k_m3))(deltas + e_up)
            e_up = deltas + e_up - c
            theta = theta - cfg.server_lr * jnp.mean(c, axis=0)
            new_hat = []
            for i in range(n):
                lo = i * k_m3
                hi = d if i == n - 1 else min((i + 1) * k_m3, d)
                sl = theta_hat[i].at[lo:hi].set(theta[lo:hi])
                new_hat.append(sl)
            theta_hat = jnp.stack(new_hat)
            ul_bits = n * topk_bits(d, k_m3)
            dl_bits = n * (d / n) * FLOAT_BITS
        else:
            raise ValueError(scheme)

        meter.add_round(ul_bits, dl_bits)
        if (t + 1) % cfg.eval_every == 0 or t == cfg.rounds - 1:
            acc = task.evaluate(theta)
            history.append({"round": t + 1, "acc": float(acc),
                            "cum_bits": meter.total_bits})

    return {"history": history, "meter": meter.summary(), "theta": theta,
            "final_acc": history[-1]["acc"] if history else float("nan"),
            "max_acc": max(h["acc"] for h in history) if history else float("nan")}
