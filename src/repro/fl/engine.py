"""The one FL round loop: local-train -> uplink -> aggregate -> downlink.

Every training loop in the repo -- the four BiCompFL variants, BiCompFL-CFL,
and all seven non-stochastic baselines -- is an :class:`EngineSpec`
(uplink channel, downlink channel, aggregator, plus block allocation and
participation policy) executed by :class:`FLEngine`.  The engine owns the
things every scheme shares and that used to be copy-pasted per loop:

* shared-randomness key schedule (round key, per-client training keys),
* partial participation (cohort sampling; inactive clients are *not*
  trained),
* the block-allocation control plane,
* periodic error-feedback synchronisation (CSER / LIEC style ``flush``),
* BitMeter accounting and evaluation history,
* deterministic fault injection (:mod:`repro.fl.faults`) with degraded
  aggregation, retransmit accounting, and crash-safe resume
  (:mod:`repro.checkpoint`).

Two execution paths (tests/test_fused_parity.py; bit-for-bit identical
under static block plans, accuracy/bits-parity within the bucketing bound
under adaptive ones):

* **host** -- a Python round loop.  Functional channels run through a
  *staged* jit of the shared round core (one compiled stage per
  (plan-shape, fault-mode) signature, cached across rounds and runs --
  the host path stopped retracing channels every round); non-functional
  channels and ``wire="audit"`` runs use the eager shell protocol.
  Adaptive allocations recompute the *exact* plan from each round's KL
  profile on the host; this path is the parity oracle for the bucketed
  fused execution.
* **fused** -- the entire multi-round run is ONE ``jax.lax.scan`` over
  rounds: channel state (error-feedback memories) is an explicit carry
  pytree threaded through the pure ``step_up`` / ``step_down`` functions,
  evaluation folds in via ``lax.cond`` on the eval schedule, and the EF
  sync flush is a ``lax.cond`` branch.  With a *static* plan the per-round
  bits are data-independent, so communication is booked host-side after
  the scan with zero device round-trips -- the only device->host transfer
  of a whole run is the stacked accuracy vector.  With an *adaptive*
  allocation the round's KL profile is computed on device (the Pallas
  ``bernoulli_kl`` reduction via ``repro.kernels.ops``), a ``lax.switch``
  selects among the allocation's precompiled bucketed plans, and the now
  data-dependent per-round bits ride out of the scan as traced f32 vectors
  that ``BitMeter.book_run`` books after the run.

Fault injection (DESIGN.md §8): ``run(..., faults=FaultPlan(...))``
precomputes the whole fault trajectory next to the cohort schedule; both
paths consume the same tables (the host loop as Python values, the fused
scan as traced masks), so the same seed produces the identical faulted
run in either mode.  Dropped / lost clients have their error-feedback
rows and ``theta_hat`` rows *carried* (masked ``where``), surviving
contributions are renormalised through ``RoundContext.up_weight``, an
all-fail round keeps ``theta_hat`` (compute-then-discard select), and
corrupted deliveries book their wasted copies into the BitMeter's
``retransmit_bits`` category -- on the wire-audit path as actual flipped
frame copies that must fail CRC.

Crash-safe resume: ``checkpoint_dir=`` + ``checkpoint_every=`` write the
full engine carry (model, per-client estimates, channel state pytrees,
BitMeter, histories, and a config blob) through the atomic
:mod:`repro.checkpoint` writer; ``resume_from=`` restores it and
continues bit-identically -- the fused path runs *segmented* scans cut
at the same checkpoint boundaries, so an interrupted-and-resumed run
replays the exact program sequence of an uninterrupted one.

Cohort sampling is precomputed as a (rounds, n_active) schedule.
``cohort_rng="numpy"`` reproduces the seed's ``default_rng(seed+17)`` draws
(bit-compatible with the legacy loops); ``cohort_rng="jax"`` derives the
cohort from the round key (``fold_in(kt, TAG_COHORT)``), making the whole
run a pure function of ``seed`` with no host RNG.

The engine reproduces the seed loops bit-for-bit at full participation
(tests/test_engine_parity.py); see DESIGN.md for the API contract.
"""
from __future__ import annotations

import json
import os
from dataclasses import asdict, dataclass
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import checkpoint as ckpt
from repro.core import mrc
from repro.core.bernoulli import bern_kl, clip01
from repro.core.bitmeter import BitMeter
from repro.kernels.ops import bernoulli_kl_profile, bernoulli_kl_total
from .channels import (BlockPlan, RoundContext, ServerUpdate, TAG_COHORT,
                       TAG_TRAIN, pin)
from .data import Dataset
from .faults import FaultPlan, fault_report


def _kl_stats(payload, priors, *, needs_profile: bool) -> Dict[str, Any]:
    """On-device KL statistics for the bucketed adaptive control plane.

    Mirrors the host loop's profile (per-parameter KL of the posterior
    against the client priors, averaged over the active cohort) without
    leaving the device.  On a real accelerator backend both allocation
    flavours run through the compiled Pallas ``bernoulli_kl`` streaming
    reduction: the *mean*-only consumers (``needs_profile=False``,
    e.g. AdaptiveAvgAllocation) take
    ``repro.kernels.ops.bernoulli_kl_total``, and the full-profile
    consumers (``needs_profile=True``, AdaptiveAllocation) take
    ``repro.kernels.ops.bernoulli_kl_profile`` (clients as the kernel's
    reduction axis).  On the CPU the kernels could only run in the Pallas
    interpreter, orders of magnitude slower than the fused XLA elementwise
    reduction, so the jnp route is used there.  Both routes agree up to
    f32 summation order.
    """
    p = clip01(priors)
    if jax.default_backend() != "cpu":
        if needs_profile:
            klp = bernoulli_kl_profile(payload, p)
            return {"profile": klp, "total": jnp.sum(klp)}
        return {"profile": None, "total": bernoulli_kl_total(payload, p)}
    klp = jnp.mean(jax.vmap(bern_kl)(payload, p), axis=0)
    return {"profile": klp if needs_profile else None,
            "total": jnp.sum(klp)}


# ---------------------------------------------------------------------------
# Fault-aware helpers shared verbatim by both execution paths.
# ---------------------------------------------------------------------------


def _cohort_mean(ctx, x):
    """Mean over the cohort axis, renormalised over survivors under faults.

    On fault-free rounds ``ctx.up_weight`` is None and this is *exactly*
    ``jnp.mean`` -- the legacy expression, bit-for-bit.  Under injected
    faults the weights zero out dropped / straggling / lost-uplink rows
    and the denominator is the survivor count (guarded against the
    all-fail round, whose result the engine discards anyway).
    """
    w = getattr(ctx, "up_weight", None)
    if w is None:
        return jnp.mean(x, axis=0)
    tot = jnp.sum(w)
    den = jnp.where(tot > 0.0, tot, 1.0)
    return jnp.tensordot(w, x, axes=1) / den


def _carry_rows(prev, new, keep):
    """Keep per-client state rows only where ``keep``; carry ``prev`` rows.

    Applied leaf-wise over a channel-state pytree: leaves whose leading
    axis is the client axis are row-masked, everything else (server-side
    state, scalars) takes the new value.  Works on traced values inside
    the fused scan and on eager arrays in the host loop alike.
    """
    if new is None:
        return None
    n = keep.shape[0]
    if prev is None:
        prev = jax.tree.map(jnp.zeros_like, new)

    def sel(p, q):
        q = jnp.asarray(q)
        if q.ndim >= 1 and q.shape[0] == n:
            k = jnp.reshape(keep, (n,) + (1,) * (q.ndim - 1))
            return jnp.where(k, q, p)
        return q

    return jax.tree.map(sel, prev, new)


def _faulted_round_bits(ul_bits, dl_bits, oh_full, rf, n_active, dl_denom):
    """Scale one round's nominal bit totals by its fault view.

    Returns ``(uplink, downlink, overhead, retransmit)`` bits.  Uplink
    bills every *delivered* sender (stragglers included -- the traffic
    happened); each corrupted copy re-bills one per-client payload into
    the retransmit category; the downlink of an all-fail round never
    leaves the server; CTRL side information reaches online clients only.
    Used identically by the host loop and the fused post-scan booking so
    both paths run the same float arithmetic.
    """
    per_up = ul_bits / n_active
    per_dn = dl_bits / dl_denom if dl_denom else 0.0
    per_oh = oh_full / len(rf.online)
    ul = per_up * float(rf.delivered_up.sum())
    rt = per_up * float(rf.up_wasted.sum())
    if rf.all_failed:
        dl = 0.0
    else:
        dl = per_dn * float(rf.delivered_dn.sum())
        rt += per_dn * float(rf.dn_wasted.sum())
    oh = per_oh * float(rf.online.sum())
    return ul, dl, oh, rt


# ---------------------------------------------------------------------------
# Aggregators: uplink output -> proposed server update.
# ---------------------------------------------------------------------------


class MeanModelAggregator:
    """BiCompFL: the mean of the conveyed posterior samples *is* the model."""

    def __call__(self, ctx, theta, up_out) -> ServerUpdate:
        return ServerUpdate(theta=_cohort_mean(ctx, up_out))


@dataclass
class MeanDeltaAggregator:
    """Conventional FL: average the (compressed) deltas, step the server."""

    server_lr: float = 1.0

    def __call__(self, ctx, theta, up_out) -> ServerUpdate:
        # The mean feeds the server step; pinned so the fused engine cannot
        # FMA-contract mean's scale into the subtraction (cf. channels.pin).
        g = pin(getattr(ctx, "pin_token", None), _cohort_mean(ctx, up_out))
        return ServerUpdate(theta=theta - self.server_lr * g, delta=g,
                            lr=self.server_lr)


# ---------------------------------------------------------------------------
# Engine.
# ---------------------------------------------------------------------------


@dataclass
class EngineSpec:
    """A complete FL scheme: who compresses what, in which direction."""

    uplink: Any
    downlink: Any
    aggregator: Any
    allocation: Any = None       # block-allocation strategy (MRC schemes)
    participation: float = 1.0   # fraction of clients active per round
    sync_period: int = 0         # 0 = never; else flush EF memories every k
    name: str = ""


class FLEngine:
    """Runs an :class:`EngineSpec` against a task and sharded dataset."""

    def __init__(self, task, spec: EngineSpec):
        self.task = task
        self.spec = spec
        # Fused-program cache (satellite of the wire PR): one compiled
        # scanned-run program per (rounds, shapes) signature, so repeated
        # ``run()`` calls -- benchmark sweeps, seed replicates -- stop
        # retracing the scan body.  Each entry holds the jitted runner and
        # the trace-time ``booked`` bit record it captured.
        self._fused_programs: Dict[Any, Any] = {}
        self.fused_trace_count = 0  # bumped at trace time (regression test)
        # Host-path stage cache: one jitted round core per (plan-shape,
        # fault-mode) signature.  The same shape signature recurs every
        # round (and across runs), so the host loop stops re-tracing the
        # channels each round -- the ROADMAP "host re-trace" item.
        self._host_jits: Dict[Any, Any] = {}
        self.host_trace_count = 0   # bumped at trace time (regression test)

    # -- fused-path eligibility -------------------------------------------

    def _functional_channels(self) -> bool:
        """Both channels speak the pure-state protocol (explicit carry)."""
        spec = self.spec
        up_ok = all(hasattr(spec.uplink, a)
                    for a in ("step_up", "init_up_state", "flush_step"))
        dn_ok = all(hasattr(spec.downlink, a)
                    for a in ("step_down", "init_down_state", "flush_step"))
        return up_ok and dn_ok

    def fused_supported(self) -> bool:
        """True when the whole run can compile to one scanned XLA program.

        Only *non-functional* channels (no ``step_up`` / ``step_down``
        protocol) force the host loop.  Adaptive allocations are fused via
        their bucketed control plane (``bucket_plans`` / ``select_bucket``
        / ``finalize_plan``); an allocation exposing neither a static plan
        nor the bucket API -- or a hand-built spec combining a
        data-dependent plan with a periodic EF flush, a pairing no
        registry scheme produces (the flush would need the aggregator's
        step size inside every switch branch) -- stays host-only.
        """
        spec = self.spec
        if spec.allocation is not None and \
                not getattr(spec.allocation, "static_plan", False):
            bucket_ok = all(hasattr(spec.allocation, a) for a in
                            ("bucket_plans", "select_bucket", "finalize_plan"))
            if not bucket_ok or spec.sync_period:
                return False
        return self._functional_channels()

    # -- cohort schedule ---------------------------------------------------

    @staticmethod
    def cohort_schedule(rounds: int, n: int, n_active: int, seed: int,
                        cohort_rng: str = "numpy") -> np.ndarray:
        """Precompute the (rounds, n_active) active-cohort table.

        ``numpy`` consumes ``default_rng(seed+17)`` exactly as the seed
        loops did (one sorted no-replacement draw per round, in round
        order), so precomputing changes nothing.  ``jax`` derives each
        round's cohort from the shared round key instead.
        """
        if cohort_rng not in ("numpy", "jax"):
            raise ValueError(cohort_rng)
        if n_active >= n:
            return np.tile(np.arange(n, dtype=np.int64), (rounds, 1))
        if cohort_rng == "numpy":
            rng = np.random.default_rng(seed + 17)
            return np.stack([np.sort(rng.choice(n, size=n_active, replace=False))
                             for _ in range(rounds)])
        base = jax.random.PRNGKey(seed)

        def one(t):
            kc = jax.random.fold_in(mrc.round_key(base, t), TAG_COHORT)
            return jnp.sort(jax.random.choice(
                kc, n, (n_active,), replace=False))

        sched = jax.vmap(one)(jnp.arange(rounds))
        return np.asarray(sched, dtype=np.int64)

    # -- the shared round core --------------------------------------------

    @staticmethod
    def _round_core(spec, plan, theta, theta_hat, up_s, dn_s, payload,
                    priors, ctx):
        """Uplink -> aggregate -> downlink at one (static-shape) plan.

        The single definition both execution paths trace -- the fused
        scan body and the host loop's staged jit -- so a faulted host
        round and a faulted fused round are the *same* compiled graph.
        Every cross-stage value is pinned through ``channels.pin`` (an
        integer-space round-trip on a traced zero) so XLA cannot
        FMA-contract across stage boundaries and break host/fused
        bit-parity.
        """
        pp = ctx.pin_token
        up_out, ul_bits, up_s = spec.uplink.step_up(
            ctx, up_s, payload, priors)
        up_out, up_s = pin(pp, (up_out, up_s))
        update = spec.aggregator(ctx, theta, up_out)
        update = ServerUpdate(theta=pin(pp, update.theta),
                              delta=pin(pp, update.delta)
                              if update.delta is not None else None,
                              lr=update.lr)
        res, dn_s = spec.downlink.step_down(
            ctx, dn_s, update, theta, theta_hat)
        theta, theta_hat, dn_s = pin(pp, (res.theta, res.theta_hat, dn_s))
        oh = plan.overhead_bits * ctx.n_clients if plan is not None else 0.0
        return theta, theta_hat, up_s, dn_s, update, ul_bits, res.bits, oh

    # -- entry point -------------------------------------------------------

    def run(self, shards: Dataset, theta0: Optional[jax.Array] = None, *,
            rounds: int, seed: int = 0, eval_every: int = 1,
            mode: str = "auto", cohort_rng: str = "numpy",
            wire: Optional[str] = None,
            faults: Optional[FaultPlan] = None,
            checkpoint_dir: Optional[str] = None,
            checkpoint_every: int = 0,
            resume_from: Optional[str] = None) -> Dict[str, Any]:
        """Run the scheme.  ``mode``: "auto" (fused when eligible), "host",
        or "fused" (raises for schemes needing the host control plane).

        ``wire="audit"`` serializes every channel payload through the
        :mod:`repro.wire` bitstream each round (encode -> decode; the
        decoded values drive the trajectory, so the run certifies the
        codecs are lossless) and reconciles the BitMeter against the
        stream; host-path only.  The report lands in ``out["wire"]`` and
        the full stream in ``out["wire_session"]``.

        ``faults=FaultPlan(...)`` injects the plan's deterministic fault
        schedule (dropouts, stragglers, frame corruption) into the run;
        the event log and summary land in ``out["faults"]``.  A plan that
        draws no fault for this run leaves the trajectory bit-identical
        to ``faults=None``.

        ``checkpoint_dir=`` (+ ``checkpoint_every=k``) saves the full
        engine state every k rounds (and at the end); ``resume_from=``
        (a checkpoint file or a directory to scan for the newest valid
        step) restores it and continues bit-identically.
        """
        task, spec = self.task, self.spec
        if wire not in (None, "audit"):
            raise ValueError(f"wire={wire!r} (expected None or 'audit')")
        if wire and mode == "fused":
            raise ValueError("wire audit runs on the host path; it cannot "
                             "be combined with mode='fused'")
        if faults is not None and not isinstance(faults, FaultPlan):
            raise ValueError(f"faults={faults!r} (expected a FaultPlan)")
        if checkpoint_every and not checkpoint_dir:
            raise ValueError("checkpoint_every needs checkpoint_dir")
        if checkpoint_every < 0:
            raise ValueError(f"checkpoint_every={checkpoint_every} < 0")
        if wire and (checkpoint_dir or resume_from):
            raise ValueError("wire audit cannot checkpoint or resume (the "
                             "session stream is not part of the saved carry)")
        if (checkpoint_dir or resume_from) and not self._functional_channels():
            raise ValueError(
                f"spec {spec.name!r} cannot checkpoint/resume: channels "
                "without the pure-state protocol have no explicit carry")
        # Stateful channels (error-feedback memories) must start fresh: a
        # spec may be run more than once.
        for chan in (spec.uplink, spec.downlink):
            reset = getattr(chan, "reset", None)
            if reset is not None:
                reset()
        n = int(shards.x.shape[0])
        theta = task.init_theta() if theta0 is None else theta0
        d = int(theta.shape[0])
        theta_hat = jnp.tile(theta[None], (n, 1))
        meter = BitMeter(
            n_clients=n, d=d,
            broadcast_downlink_shareable=getattr(
                spec.downlink, "broadcast_shareable", True))
        n_active = max(1, int(round(spec.participation * n)))
        schedule = self.cohort_schedule(rounds, n, n_active, seed, cohort_rng)

        # Fault schedule: precomputed like the cohort schedule, before any
        # round work.  ``views`` stays None when the drawn schedule is
        # fault-free, keeping the run on the exact legacy code paths.
        fsched = views_all = views = None
        if faults is not None:
            fsched = faults.schedule(rounds, n)
            dl_rec = getattr(spec.downlink, "downlink_recipients", "all")
            views_all = fsched.run_views(schedule, dl_rec)
            if any(v.faulty or v.all_failed for v in views_all):
                views = views_all
        if views is not None and not wire and not self._functional_channels():
            raise ValueError(
                f"spec {spec.name!r} cannot run under faults without the "
                "pure-state channel protocol (state rows must be carried "
                "explicitly) or a wire session")
        if views is not None and wire:
            for role, chan in (("uplink", spec.uplink),
                               ("downlink", spec.downlink)):
                if not (hasattr(chan, "export_state")
                        and hasattr(chan, "import_state")):
                    raise ValueError(
                        f"spec {spec.name!r} cannot run faulted wire audit: "
                        f"{role} channel lacks export_state/import_state")

        if mode not in ("auto", "host", "fused"):
            raise ValueError(mode)
        fused_ok = self.fused_supported()
        if mode == "fused" and not fused_ok:
            raise ValueError(
                f"spec {spec.name!r} needs the host control plane "
                "(non-functional channels, an allocation without the bucket "
                "API, or a data-dependent plan combined with an EF flush)")
        fused = fused_ok and mode != "host" and not wire

        cfg_blob = None
        if checkpoint_dir or resume_from:
            cfg_blob = self._config_blob(rounds=rounds, seed=seed,
                                         eval_every=eval_every,
                                         cohort_rng=cohort_rng, n=n, d=d,
                                         faults=faults)
        start_round, carry_in, history0 = 0, None, None
        if resume_from:
            start_round, theta, theta_hat, carry_in, history0 = \
                self._load_resume(resume_from, cfg_blob, meter)

        if fused:
            out = self._run_fused(shards, theta, theta_hat, meter,
                                  rounds=rounds, seed=seed,
                                  eval_every=eval_every, schedule=schedule,
                                  views=views, start_round=start_round,
                                  carry_in=carry_in, history=history0,
                                  checkpoint_dir=checkpoint_dir,
                                  checkpoint_every=checkpoint_every,
                                  cfg_blob=cfg_blob)
        else:
            session = None
            if wire:
                from repro.wire import WireSession, scheme_wire_id
                session = WireSession(
                    scheme_id=scheme_wire_id(spec.name or "unnamed"))
            out = self._run_host(shards, theta, theta_hat, meter,
                                 rounds=rounds, seed=seed,
                                 eval_every=eval_every, schedule=schedule,
                                 session=session, views=views, fsched=fsched,
                                 start_round=start_round, carry_in=carry_in,
                                 history=history0,
                                 checkpoint_dir=checkpoint_dir,
                                 checkpoint_every=checkpoint_every,
                                 cfg_blob=cfg_blob)
            if session is not None:
                out["wire"] = session.reconcile(meter)
                out["wire_session"] = session
        out["active_schedule"] = schedule
        out["mode"] = "fused" if fused else "host"
        if faults is not None:
            rt_by_round = [h.get("retransmit_bits", 0.0)
                           for h in meter.history]
            out["faults"] = fault_report(faults, views_all, rt_by_round)
        return out

    # -- checkpoint / resume ----------------------------------------------

    def _config_blob(self, *, rounds, seed, eval_every, cohort_rng, n, d,
                     faults) -> np.ndarray:
        """Run configuration as a uint8 JSON blob (a checkpoint leaf).

        Saved with every checkpoint and compared bytewise on resume: a
        checkpoint only resumes the *same* run (spec, rounds, seed, fault
        plan), because everything the engine recomputes from scratch --
        cohort schedule, fault schedule, round keys -- must re-derive
        identically for the continuation to be bit-exact.
        """
        spec = self.spec
        cfg = {
            "kind": "fl-engine-checkpoint",
            "format": 1,
            "spec": spec.name,
            "rounds": int(rounds),
            "seed": int(seed),
            "eval_every": int(eval_every),
            "cohort_rng": cohort_rng,
            "n": int(n),
            "d": int(d),
            "participation": float(spec.participation),
            "sync_period": int(spec.sync_period),
            "faults": None if faults is None else asdict(faults),
        }
        raw = json.dumps(cfg, sort_keys=True).encode("utf-8")
        return np.frombuffer(raw, np.uint8).copy()

    def _save_state(self, directory, next_round, theta, theta_hat, up_s,
                    dn_s, meter, history, cfg_blob) -> None:
        """Write the full engine carry as one atomic per-step checkpoint."""
        mh = meter.history
        state = {
            "config": cfg_blob,
            "next_round": np.int64(next_round),
            "theta": np.asarray(theta),
            "theta_hat": np.asarray(theta_hat),
            "up_state": jax.tree.map(np.asarray, up_s),
            "dn_state": jax.tree.map(np.asarray, dn_s),
            "meter": {
                "uplink_bits": np.float64(meter.uplink_bits),
                "downlink_bits": np.float64(meter.downlink_bits),
                "retransmit_bits": np.float64(meter.retransmit_bits),
                "rounds": np.int64(meter.rounds),
                "hist_round": np.asarray([h["round"] for h in mh], np.int64),
                "hist_up": np.asarray([h["uplink_bits"] for h in mh],
                                      np.float64),
                "hist_dn": np.asarray([h["downlink_bits"] for h in mh],
                                      np.float64),
                "hist_rt": np.asarray([h.get("retransmit_bits", 0.0)
                                       for h in mh], np.float64),
                "hist_cum": np.asarray([h["cum_bits"] for h in mh],
                                       np.float64),
            },
            "history": {
                "round": np.asarray([h["round"] for h in history], np.int64),
                "acc": np.asarray([h["acc"] for h in history], np.float64),
                "cum_bits": np.asarray([h["cum_bits"] for h in history],
                                       np.float64),
                "bpp": np.asarray([h["bpp_so_far"] for h in history],
                                  np.float64),
            },
        }
        ckpt.save_step(directory, state, int(next_round))

    def _load_resume(self, resume_from, cfg_blob, meter):
        """Restore ``(start_round, theta, theta_hat, carry, history)``.

        ``resume_from`` is a checkpoint file, or a directory whose newest
        *valid* step checkpoint is chosen (torn files are skipped with a
        warning by :func:`repro.checkpoint.latest`).  The saved config
        blob must match this run's exactly.
        """
        if os.path.isdir(resume_from):
            path, _ = ckpt.latest(resume_from)
            if path is None:
                raise ValueError(
                    f"resume_from={resume_from!r}: no valid checkpoint found")
        else:
            path = resume_from
        state, _ = ckpt.load(path)
        saved = bytes(np.asarray(state["config"], np.uint8))
        if saved != bytes(np.asarray(cfg_blob, np.uint8)):
            raise ValueError(
                f"checkpoint {path} was saved by a different run "
                "configuration (spec/rounds/seed/faults must be identical "
                "to resume)")
        m = state["meter"]
        meter.uplink_bits = float(m["uplink_bits"])
        meter.downlink_bits = float(m["downlink_bits"])
        meter.retransmit_bits = float(m["retransmit_bits"])
        meter.rounds = int(m["rounds"])
        meter.history = []
        for r, u, dl, rt, cum in zip(m["hist_round"], m["hist_up"],
                                     m["hist_dn"], m["hist_rt"],
                                     m["hist_cum"]):
            entry = {"round": int(r), "uplink_bits": float(u),
                     "downlink_bits": float(dl), "cum_bits": float(cum)}
            if rt:  # key present only when nonzero, as add_round writes it
                entry["retransmit_bits"] = float(rt)
            meter.history.append(entry)
        h = state["history"]
        history0 = [{"round": int(r), "acc": float(a), "cum_bits": float(c),
                     "bpp_so_far": float(b)}
                    for r, a, c, b in zip(h["round"], h["acc"],
                                          h["cum_bits"], h["bpp"])]
        theta = jnp.asarray(state["theta"])
        theta_hat = jnp.asarray(state["theta_hat"])
        carry = (jax.tree.map(jnp.asarray, state["up_state"]),
                 jax.tree.map(jnp.asarray, state["dn_state"]))
        return (int(np.asarray(state["next_round"])), theta, theta_hat,
                carry, history0)

    # -- host loop ---------------------------------------------------------

    def _stage_round(self, plan, faulted, n, d, n_active):
        """Cached jit of the shared round core for the host loop.

        Keyed on the plan's *shape* (block size / count / segmented or
        not), the fault mode, and the run dims -- everything that changes
        the traced graph.  Round index, key, cohort, segment ids and
        fault weights ride in as traced arguments, so every round of a
        run (and repeated runs) reuse one compiled stage.  The returned
        ``rec`` dict holds the trace-time Python-float bit totals (bits
        are data-independent under a static plan; ``float()`` on a traced
        value would fail loudly).
        """
        pkey = None if plan is None else (
            plan.size, int(plan.n_blocks), plan.seg_ids is not None,
            getattr(plan, "billable_blocks", None))
        key = ("round", pkey, faulted, n, d, n_active)
        hit = self._host_jits.get(key)
        if hit is not None:
            return hit
        spec = self.spec
        rec: Dict[str, float] = {}
        has_plan = plan is not None
        size = plan.size if has_plan else None
        n_blocks = int(plan.n_blocks) if has_plan else None
        billable = getattr(plan, "billable_blocks", None) if has_plan else None

        def stage(kt, t, active, ptok, seg, w, theta, theta_hat, up_s, dn_s,
                  payload, priors):
            self.host_trace_count += 1  # Python side effect: trace-time only
            p = None
            if has_plan:
                p = BlockPlan(size=size, n_blocks=n_blocks, seg_ids=seg,
                              overhead_bits=0.0, billable_blocks=billable)
            ctx = RoundContext(t=t, key=kt, n_clients=n, d=d, active=active,
                               plan=p, pin_token=ptok, up_weight=w)
            th, thh, us, ds, update, ul_bits, dl_bits, _ = self._round_core(
                spec, p, theta, theta_hat, up_s, dn_s, payload, priors, ctx)
            rec["ul"] = float(ul_bits)
            rec["dl"] = float(dl_bits)
            rec["lr"] = float(update.lr)
            return th, thh, us, ds

        entry = (jax.jit(stage), rec)
        self._host_jits[key] = entry
        return entry

    def _run_host(self, shards, theta, theta_hat, meter, *, rounds, seed,
                  eval_every, schedule, session=None, views=None,
                  fsched=None, start_round=0, carry_in=None, history=None,
                  checkpoint_dir=None, checkpoint_every=0,
                  cfg_blob=None) -> Dict[str, Any]:
        task, spec = self.task, self.spec
        n, d = meter.n_clients, meter.d
        n_active = schedule.shape[1]
        base = jax.random.PRNGKey(seed)
        history = list(history) if history else []
        faulted = views is not None
        dl_rec = getattr(spec.downlink, "downlink_recipients", "all")
        dl_denom = n if dl_rec == "all" else n_active
        if session is not None:
            self._check_wire_support()
        # Functional channels run through the cached staged jit (explicit
        # state carry, fault masks applied host-side between stages); the
        # wire-audit path and non-functional channels keep the eager shell
        # protocol.
        staged = session is None and self._functional_channels()
        up_s = dn_s = None
        if staged:
            if carry_in is not None:
                up_s, dn_s = carry_in
            else:
                up_s = spec.uplink.init_up_state(n, d)
                dn_s = spec.downlink.init_down_state(n, d)

        for t in range(start_round, rounds):
            kt = mrc.round_key(base, t)
            active = schedule[t]
            rf = views[t] if faulted else None
            msgs = []  # this round's wire traffic (audit mode only)

            # ---- local training: only the active cohort ------------------
            train_keys = jax.random.split(jax.random.fold_in(kt, TAG_TRAIN), n)
            if n_active < n:
                priors = theta_hat[active]
                xs, ys, keys = (shards.x[active], shards.y[active],
                                train_keys[active])
            else:  # full participation: no device-side gather/copy needed
                priors, xs, ys, keys = theta_hat, shards.x, shards.y, train_keys
            payload = jax.vmap(task.local_train)(priors, xs, ys, keys)

            # ---- block allocation (host-side control plane) --------------
            plan = None
            if spec.allocation is not None:
                kl = None
                if getattr(spec.allocation, "needs_kl", True):
                    kl = np.asarray(jnp.mean(jax.vmap(bern_kl)(
                        payload, clip01(priors)), axis=0))
                size, n_blocks, seg_ids, overhead = spec.allocation.plan(kl, d)
                plan = BlockPlan(size=size, n_blocks=n_blocks,
                                 seg_ids=seg_ids, overhead_bits=overhead)
                if session is not None:
                    # The plan side information crosses the wire as one CTRL
                    # frame per client (the meter books overhead_bits * n);
                    # the decoded plan -- not the host object -- drives the
                    # round, certifying the header codec.  Under faults the
                    # CTRL link is protected signalling: never corrupted,
                    # but dropped clients miss their copy.
                    ctrl = self._encode_plan_msgs(plan, n)
                    plan = self._decode_plan_msg(ctrl[0], d)
                    msgs += [m for m in ctrl
                             if not faulted or rf.online[m.sender]]

            if staged:
                tj = jnp.asarray(t, jnp.int32)
                aj = jnp.asarray(active)
                ptok = jnp.zeros((), jnp.int32)  # pins must fire inside jit
                seg = None if plan is None or plan.seg_ids is None \
                    else jnp.asarray(plan.seg_ids)
                w = jnp.asarray(rf.up_weight) if faulted else None
                fn, rec = self._stage_round(plan, faulted, n, d, n_active)
                th, thh, us, ds = fn(kt, tj, aj, ptok, seg, w, theta,
                                     theta_hat, up_s, dn_s, payload, priors)
                ul_bits, dl_bits, lr = rec["ul"], rec["dl"], rec["lr"]
                if faulted:
                    # Carried, not corrupted: dropped/lost rows keep their
                    # pre-round EF state and theta_hat estimate; an
                    # all-fail round discards the whole computed step.
                    us = _carry_rows(up_s, us, jnp.asarray(rf.delivered_up))
                    thh = jnp.where(jnp.asarray(rf.delivered_dn)[:, None],
                                    thh, theta_hat)
                    if rf.all_failed:
                        th, thh, us, ds = theta, theta_hat, up_s, dn_s
                theta, theta_hat, up_s, dn_s = th, thh, us, ds
                oh_full = plan.overhead_bits * n if plan is not None else 0.0
                if faulted:
                    ul_r, dl_r, oh_r, rt_r = _faulted_round_bits(
                        ul_bits, dl_bits, oh_full, rf, n_active, dl_denom)
                else:
                    ul_r, dl_r, oh_r, rt_r = ul_bits, dl_bits, oh_full, 0.0
                # ---- periodic EF synchronisation (CSER / LIEC) -----------
                # The flush is protected signalling: exempt from faults,
                # booked unscaled.
                if spec.sync_period and (t + 1) % spec.sync_period == 0:
                    r_up, b_up, up_s = spec.uplink.flush_step(up_s, n, d)
                    r_dn, b_dn, dn_s = spec.downlink.flush_step(dn_s, n, d)
                    theta = theta - lr * (r_up + r_dn)
                    theta_hat = jnp.tile(theta[None], (n, 1))
                    ul_r += b_up
                    dl_r += b_dn
                meter.add_round(ul_r, dl_r, overhead_bits=oh_r,
                                retransmit_bits=rt_r)
            else:
                theta, theta_hat = self._shell_round(
                    t, kt, active, plan, payload, priors, theta, theta_hat,
                    meter, session, msgs, rf, fsched, n, d, n_active,
                    dl_denom)
            if session is not None:
                session.add(msgs, round=t)

            if (t + 1) % eval_every == 0 or t == rounds - 1:
                acc = task.evaluate(theta)
                history.append({"round": t + 1, "acc": float(acc),
                                "cum_bits": meter.total_bits,
                                "bpp_so_far": meter.total_bpp})
            if staged and checkpoint_dir and (
                    (checkpoint_every and (t + 1) % checkpoint_every == 0)
                    or t + 1 == rounds):
                self._save_state(checkpoint_dir, t + 1, theta, theta_hat,
                                 up_s, dn_s, meter, history, cfg_blob)

        return self._result(history, meter, theta, theta_hat)

    def _shell_round(self, t, kt, active, plan, payload, priors, theta,
                     theta_hat, meter, session, msgs, rf, fsched, n, d,
                     n_active, dl_denom):
        """One eager shell-protocol round (wire audit / non-functional).

        Appends this round's frames to ``msgs`` (mutated in place) and
        books the meter.  ``rf`` is the round's fault view or None; a
        faulted shell round always has a wire session (enforced in
        ``run``), injects real corrupted frame copies, and books bits
        from the stream itself so the session reconciles exactly.
        """
        spec = self.spec
        faulted = rf is not None
        if faulted:
            up_snap = spec.uplink.export_state()
            dn_snap = spec.downlink.export_state()
            n_wasted0 = len(session.wasted)
        ctx = RoundContext(t=t, key=kt, n_clients=n, d=d, active=active,
                           plan=plan,
                           up_weight=jnp.asarray(rf.up_weight)
                           if faulted else None)

        # ---- uplink -> aggregate -> downlink -----------------------------
        if session is None:
            up_out, ul_bits = spec.uplink.transmit(ctx, payload, priors)
        else:
            up_out, ul_bits, up_msgs = spec.uplink.transmit_wire(
                ctx, payload, priors)
            up_out = spec.uplink.decode_up(ctx, up_msgs, priors)
            if faulted:
                spec.uplink.import_state(_carry_rows(
                    up_snap, spec.uplink.export_state(),
                    jnp.asarray(rf.delivered_up)))
                msgs += self._wire_deliver(
                    session, fsched, rf, t, up_msgs, owner="sender", link=0,
                    sched=rf.senders, ok=rf.delivered_up,
                    wasted=rf.up_wasted)
            else:
                msgs += up_msgs
        update = spec.aggregator(ctx, theta, up_out)
        if session is None:
            theta, theta_hat, dl_bits = spec.downlink.distribute(
                ctx, update, theta, theta_hat)
        elif faulted and rf.all_failed:
            # Compute-then-discard: the server aborts before broadcasting,
            # every client (and the channel state) keeps its pre-round
            # view; only the uplink traffic that did happen is billed.
            spec.uplink.import_state(up_snap)
            spec.downlink.import_state(dn_snap)
            dl_bits = 0.0
        else:
            from .channels import WireEnv
            _, dn_msgs = spec.downlink.distribute_wire(
                ctx, update, theta, theta_hat, up_msgs)
            env = WireEnv(uplink=spec.uplink, aggregator=spec.aggregator,
                          priors=priors, up_msgs=up_msgs, update=update)
            new_th, new_hat, dl_bits = spec.downlink.decode_down(
                ctx, dn_msgs, theta, theta_hat, env)
            if faulted:
                theta = new_th
                theta_hat = jnp.where(jnp.asarray(rf.delivered_dn)[:, None],
                                      new_hat, theta_hat)
                msgs += self._wire_deliver(
                    session, fsched, rf, t, dn_msgs, owner="recipient",
                    link=1, sched=rf.nominal_recv & rf.online,
                    ok=rf.delivered_dn, wasted=rf.dn_wasted)
            else:
                theta, theta_hat = new_th, new_hat
                msgs += dn_msgs

        # ---- periodic EF synchronisation (CSER / LIEC) -------------------
        if spec.sync_period and (t + 1) % spec.sync_period == 0:
            if session is None:
                r_up, b_up = spec.uplink.flush(n, d)
            else:
                r_up, b_up, fl_msgs = spec.uplink.flush_wire(n, d)
                if fl_msgs:
                    r_up = spec.uplink.decode_flush_up(fl_msgs, n, d)
                msgs += fl_msgs
            r_dn, b_dn = spec.downlink.flush(n, d)
            # flush at the aggregator's step size (update.lr), so a
            # hand-built spec cannot desync the reset from the rounds
            theta = theta - update.lr * (r_up + r_dn)
            theta_hat = jnp.tile(theta[None], (n, 1))
            ul_bits += b_up
            dl_bits += b_dn
            if session is not None and b_dn:
                # The downlink flush re-broadcasts the synced model: n
                # dense frames of the post-flush theta, n * d * 32 bits
                # == every stateful downlink's booked flush cost.  The
                # decoded broadcast drives the trajectory.
                fd_msgs, theta = self._flush_down_msgs(theta, n, d, b_dn)
                theta_hat = jnp.tile(theta[None], (n, 1))
                msgs += fd_msgs

        if faulted:
            # Book straight from the frames that actually hit the stream
            # (CTRL overhead rides the uplink direction), so the session
            # reconcile is exact by construction.
            from repro.wire import DOWNLINK_DIRS, UPLINK_DIRS
            ul_r = float(sum(m.payload_bits for m in msgs
                             if m.direction in UPLINK_DIRS))
            dl_r = float(sum(m.payload_bits for m in msgs
                             if m.direction in DOWNLINK_DIRS))
            rt_r = float(sum(wa.payload_bits
                             for wa in session.wasted[n_wasted0:]))
            meter.add_round(ul_r, dl_r, retransmit_bits=rt_r)
        else:
            overhead_bits = plan.overhead_bits * n if plan is not None else 0.0
            meter.add_round(ul_bits, dl_bits, overhead_bits=overhead_bits)
        return theta, theta_hat

    def _wire_deliver(self, session, fsched, rf, t, msgs, *, owner, link,
                      sched, ok, wasted):
        """Route one direction's frames through the faulty link.

        For every scheduled frame, materialize each corrupted copy the
        fault schedule drew (flip the scheduled bit, *prove* the CRC
        rejects it, book it as a wasted attempt), then deliver the clean
        frame iff the retry budget survived.  Returns the delivered
        frames.
        """
        from repro.wire import Message, WireError
        from .faults import corrupt_copy
        delivered = []
        for m in msgs:
            cid = getattr(m, owner)
            if not sched[cid]:
                continue
            for a in range(int(wasted[cid])):
                stamped = Message(direction=m.direction, sender=m.sender,
                                  recipient=m.recipient, payload=m.payload,
                                  payload_bits=m.payload_bits, round=t,
                                  scheme_id=session.scheme_id)
                raw = stamped.to_bytes()
                bit = fsched.flip_bit(t, cid, link, a, 8 * len(raw))
                try:
                    Message.from_bytes(corrupt_copy(raw, bit))
                except WireError:
                    pass
                else:
                    raise AssertionError(
                        f"corrupted frame copy (round {t}, client {cid}, "
                        f"bit {bit}) parsed cleanly: the CRC failed to "
                        "catch the flip")
                session.add_wasted(stamped, round=t, attempt=a,
                                   flipped_bit=bit)
            if ok[cid]:
                delivered.append(m)
        return delivered

    # -- wire-audit helpers ------------------------------------------------

    def _check_wire_support(self) -> None:
        spec = self.spec
        missing = [a for a in ("transmit_wire", "decode_up")
                   if not hasattr(spec.uplink, a)]
        missing += [a for a in ("distribute_wire", "decode_down")
                    if not hasattr(spec.downlink, a)]
        if spec.allocation is not None and not all(
                hasattr(spec.allocation, a)
                for a in ("encode_plan", "decode_plan")):
            missing.append("allocation.encode_plan/decode_plan")
        if missing:
            raise ValueError(
                f"spec {spec.name!r} cannot be wire-audited: missing "
                f"{missing}")
        # Fail before any round work: a non-power-of-two n_is books
        # fractional bits per index and would only surface as a
        # WireCapacityError from codecs.index_width mid-run.
        from repro.wire.codecs import WireCapacityError, index_width
        for role, chan in (("uplink", spec.uplink),
                           ("downlink", spec.downlink)):
            n_is = getattr(chan, "n_is", None)
            if n_is is None:
                continue
            try:
                index_width(n_is)
            except WireCapacityError as e:
                raise ValueError(
                    f"spec {spec.name!r} cannot be wire-audited: {role} "
                    f"channel {type(chan).__name__} has n_is={n_is}, "
                    "which books fractional bits per MRC index; wire "
                    "codecs need a power of two") from e

    def _encode_plan_msgs(self, plan, n):
        from repro.wire import DIR_CTRL, BitWriter, SERVER, Message
        w = BitWriter()
        self.spec.allocation.encode_plan(plan, w)
        payload, nbits = w.getvalue(), w.bits_written
        return [Message(direction=DIR_CTRL, sender=cid, recipient=SERVER,
                        payload=payload, payload_bits=nbits)
                for cid in range(n)]

    def _decode_plan_msg(self, msg, d):
        from repro.wire import BitReader
        r = BitReader(msg.payload, msg.payload_bits)
        plan = self.spec.allocation.decode_plan(r, d)
        r.expect_exhausted()
        return plan

    def _flush_down_msgs(self, theta, n, d, b_dn):
        from repro.wire import DIR_FLUSH_DOWN, BitWriter, BitReader, \
            SERVER, Message
        from repro.wire import codecs as wcodecs
        if b_dn != n * d * 32:
            raise ValueError(
                f"downlink flush books {b_dn} bits; the wire layer only "
                f"knows the dense re-broadcast protocol ({n * d * 32} bits)")
        w = BitWriter()
        wcodecs.put_dense(w, np.asarray(theta))
        payload, nbits = w.getvalue(), w.bits_written
        msgs = [Message(direction=DIR_FLUSH_DOWN, sender=SERVER,
                        recipient=cid, payload=payload, payload_bits=nbits)
                for cid in range(n)]
        r = BitReader(msgs[0].payload, msgs[0].payload_bits)
        theta = jnp.asarray(wcodecs.get_dense(r, d))
        r.expect_exhausted()
        return msgs, theta

    # -- fused loop: the whole run is one lax.scan over rounds -------------

    def _build_fused(self, *, rounds, n, d, n_active, faulted=False):
        """Build (jitted runner, trace-time booked-bits record) for one
        run signature.  Everything round-varying (seed key, cohort
        schedule, eval/flush masks, fault masks, carry, model/dataset
        arrays) is a runner *argument*; the spec, plans and shapes are
        baked into the trace.  With ``faulted`` the scan consumes the
        precomputed fault tables as extra per-round xs (weights, keep
        masks, the all-fail flag) -- the identical tables the host loop
        reads, so both modes produce the same faulted trajectory.
        """
        task, spec = self.task, self.spec
        full = n_active == n
        alloc = spec.allocation
        adaptive = alloc is not None and \
            not getattr(alloc, "static_plan", False)
        if adaptive:
            # Bucketed control plane: one lax.switch branch per static plan.
            plans = alloc.bucket_plans(d)
        elif alloc is not None:  # static: plan once for all rounds
            size, n_blocks, seg_ids, overhead = alloc.plan(None, d)
            plans = [BlockPlan(size=size, n_blocks=n_blocks, seg_ids=seg_ids,
                               overhead_bits=overhead)]
        else:
            plans = [None]

        # Static plans: bits are data-independent, so the single trace of
        # the scan body records the per-round (and per-flush) totals as
        # plain floats and the meter never touches the device.  Adaptive
        # plans: bits depend on the round's bucket, so the scan emits them
        # as traced f32 per-round vectors instead.
        booked: Dict[str, Any] = {}

        def run_fn(base, carry0, sx, sy, xs_all):
            self.fused_trace_count += 1  # Python side effect: trace-time only

            def body(carry, xs):
                theta, theta_hat, up_s, dn_s = carry
                prev = carry  # pre-round view: what faults carry forward
                kt = mrc.round_key(base, xs["t"])
                active = xs["active"]
                pp = xs["pin"]  # traced int32 zero: the rounding pin token
                w = xs["w"] if faulted else None

                train_keys = jax.random.split(
                    jax.random.fold_in(kt, TAG_TRAIN), n)
                if full:
                    priors, bx, by, keys = theta_hat, sx, sy, train_keys
                else:
                    priors = theta_hat[active]
                    bx, by, keys = sx[active], sy[active], train_keys[active]
                payload = pin(pp, jax.vmap(task.local_train)(
                    priors, bx, by, keys))

                def make_ctx(plan):
                    return RoundContext(t=xs["t"], key=kt, n_clients=n, d=d,
                                        active=active, plan=plan,
                                        pin_token=pp, up_weight=w)

                if adaptive:
                    stats = _kl_stats(payload, priors,
                                      needs_profile=getattr(
                                          alloc, "needs_profile", True))
                    bidx = alloc.select_bucket(stats, d)

                    def make_branch(template):
                        def branch(op):
                            th, thh, us, ds = op
                            plan = alloc.finalize_plan(template, stats, d)
                            th, thh, us, ds, _, ulb, dlb, oh = \
                                self._round_core(spec, plan, th, thh, us, ds,
                                                 payload, priors,
                                                 make_ctx(plan))
                            bits = tuple(jnp.asarray(b, jnp.float32)
                                         for b in (ulb, dlb, oh))
                            return th, thh, us, ds, bits
                        return branch

                    theta, theta_hat, up_s, dn_s, bits = jax.lax.switch(
                        bidx, [make_branch(p) for p in plans],
                        (theta, theta_hat, up_s, dn_s))
                    update = None
                else:
                    theta, theta_hat, up_s, dn_s, update, ul_bits, dl_bits, \
                        oh = self._round_core(spec, plans[0], theta,
                                              theta_hat, up_s, dn_s, payload,
                                              priors, make_ctx(plans[0]))
                    booked["round"] = (ul_bits, dl_bits, oh)
                    bits = ()

                if faulted:
                    # Same masking order as the host loop: theta_hat rows
                    # that missed the downlink keep the pre-round value,
                    # EF rows of undelivered uplinks are carried, and the
                    # whole step is discarded on an all-fail round.
                    theta_hat = jnp.where(xs["recv"][:, None], theta_hat,
                                          prev[1])
                    up_s = _carry_rows(prev[2], up_s, xs["keep_up"])
                    ok = xs["ok"]
                    theta, theta_hat, up_s, dn_s = jax.tree.map(
                        lambda nw, od: jnp.where(ok, nw, od),
                        (theta, theta_hat, up_s, dn_s), prev)

                if not adaptive and spec.sync_period:
                    def do_flush(op):
                        th, thh, us, ds = op
                        r_up, b_up, us = spec.uplink.flush_step(us, n, d)
                        r_dn, b_dn, ds = spec.downlink.flush_step(
                            ds, n, d)
                        booked["flush"] = (b_up, b_dn)
                        # residual means
                        r_up, r_dn = pin(pp, (r_up, r_dn))
                        th = th - update.lr * (r_up + r_dn)
                        return pin(pp, (th, jnp.tile(th[None], (n, 1)),
                                        us, ds))

                    theta, theta_hat, up_s, dn_s = jax.lax.cond(
                        xs["flush"], do_flush, lambda op: op,
                        (theta, theta_hat, up_s, dn_s))

                acc = jax.lax.cond(
                    xs["eval"],
                    lambda th: jnp.asarray(task.evaluate(th), jnp.float32),
                    lambda th: jnp.full((), jnp.nan, jnp.float32), theta)
                return (theta, theta_hat, up_s, dn_s), (acc,) + bits

            return jax.lax.scan(body, carry0, xs_all)

        return jax.jit(run_fn), booked

    def _run_fused(self, shards, theta, theta_hat, meter, *, rounds, seed,
                   eval_every, schedule, views=None, start_round=0,
                   carry_in=None, history=None, checkpoint_dir=None,
                   checkpoint_every=0, cfg_blob=None) -> Dict[str, Any]:
        spec = self.spec
        n, d = meter.n_clients, meter.d
        n_active = schedule.shape[1]
        alloc = spec.allocation
        adaptive = alloc is not None and \
            not getattr(alloc, "static_plan", False)
        faulted = views is not None
        dl_rec = getattr(spec.downlink, "downlink_recipients", "all")
        dl_denom = n if dl_rec == "all" else n_active

        eval_mask = np.zeros(rounds, bool)
        eval_mask[eval_every - 1::eval_every] = True
        if rounds:
            eval_mask[-1] = True
        flush_mask = np.zeros(rounds, bool)
        if spec.sync_period:
            flush_mask[spec.sync_period - 1::spec.sync_period] = True

        if carry_in is not None:
            up_s0, dn_s0 = carry_in
        else:
            up_s0 = spec.uplink.init_up_state(n, d)
            dn_s0 = spec.downlink.init_down_state(n, d)
        carry = (theta, theta_hat, up_s0, dn_s0)

        xs_full = {"t": jnp.arange(rounds, dtype=jnp.int32),
                   "active": jnp.asarray(schedule),
                   "eval": jnp.asarray(eval_mask),
                   "flush": jnp.asarray(flush_mask),
                   "pin": jnp.zeros(rounds, jnp.int32)}
        if faulted:
            xs_full["w"] = jnp.asarray(
                np.stack([v.up_weight for v in views]))
            xs_full["keep_up"] = jnp.asarray(
                np.stack([v.delivered_up for v in views]))
            xs_full["recv"] = jnp.asarray(
                np.stack([v.delivered_dn for v in views]))
            xs_full["ok"] = jnp.asarray(
                np.asarray([not v.all_failed for v in views]))

        # Checkpoint boundaries segment the scan: an uninterrupted
        # checkpointed run and a killed-and-resumed one execute the same
        # program sequence over the same carries, hence are bit-identical.
        bounds = set()
        if checkpoint_dir and checkpoint_every:
            first = ((start_round // checkpoint_every) + 1) * checkpoint_every
            bounds = set(range(first, rounds, checkpoint_every))
        cuts = sorted(bounds | {rounds})
        history = list(history) if history else []
        base = jax.random.PRNGKey(seed)
        s = start_round
        if s >= rounds:
            return self._result(history, meter, theta, theta_hat)
        for e in cuts:
            if e <= s:
                continue
            L = e - s
            # One compiled program per segment signature: the seed, cohort
            # schedule, fault tables and eval/flush masks ride in as
            # *data*, so seed replicates and eval-cadence changes hit the
            # cache; only a shape change (segment length, client count,
            # model size, dataset shard dims, fault mode) builds a new
            # program.
            sig = (L, n, d, n_active, faulted,
                   tuple(shards.x.shape), str(shards.x.dtype),
                   tuple(shards.y.shape), str(shards.y.dtype),
                   tuple(theta.shape), str(theta.dtype))
            prog = self._fused_programs.get(sig)
            if prog is None:
                prog = self._build_fused(rounds=L, n=n, d=d,
                                         n_active=n_active, faulted=faulted)
                self._fused_programs[sig] = prog
            fn, booked = prog
            xs = {k: v[s:e] for k, v in xs_full.items()}
            carry, outs = fn(base, carry, shards.x, shards.y, xs)
            seg_eval = eval_mask[s:e]

            if adaptive:
                # Traced-bits booking: the scan's stacked per-round bit
                # totals are the only extra device->host transfer.  They
                # are exact as long as they stay below 2**24 -- every term
                # is an integer times log2 of a pow2 n_is, and f32
                # represents integers exactly up to there -- so guard the
                # bound loudly instead of letting the accounting drift
                # silently at larger scales.
                accs, ul, dl, oh = (np.asarray(o) for o in outs)
                if max((float(np.max(np.abs(v))) if v.size else 0.0)
                       for v in (ul, dl, oh)) >= 2.0 ** 24:
                    raise OverflowError(
                        "per-round traced bits exceed the f32 integer-exact "
                        "range (2**24); run mode='host' for exact accounting "
                        "at this scale")
                ul64 = np.asarray(ul, np.float64)
                dl64 = np.asarray(dl, np.float64)
                oh64 = np.asarray(oh, np.float64)
                if faulted:
                    rows = [_faulted_round_bits(
                        float(ul64[i]), float(dl64[i]), float(oh64[i]),
                        views[s + i], n_active, dl_denom)
                        for i in range(L)]
                    snaps = meter.book_run(
                        [r[0] for r in rows], [r[1] for r in rows],
                        overhead_bits=[r[2] for r in rows],
                        retransmit_bits=[r[3] for r in rows],
                        snapshot_mask=seg_eval)
                else:
                    snaps = meter.book_run(ul64, dl64, overhead_bits=oh64,
                                           snapshot_mask=seg_eval)
            else:
                # Host-side booking with zero device involvement.
                (accs,) = outs
                accs = np.asarray(accs)
                ul_base, dl_base, oh = booked["round"]
                fl_up, fl_dn = booked.get("flush", (0.0, 0.0))
                if faulted:
                    uls, dls, ohs, rts = [], [], [], []
                    for t in range(s, e):
                        u_, d_, o_, r_ = _faulted_round_bits(
                            ul_base, dl_base, oh, views[t], n_active,
                            dl_denom)
                        if flush_mask[t]:  # flush is protected: unscaled
                            u_ += fl_up
                            d_ += fl_dn
                        uls.append(u_)
                        dls.append(d_)
                        ohs.append(o_)
                        rts.append(r_)
                    snaps = meter.book_run(uls, dls, overhead_bits=ohs,
                                           retransmit_bits=rts,
                                           snapshot_mask=seg_eval)
                else:
                    snaps = meter.book_run(
                        [ul_base + (fl_up if flush_mask[t] else 0.0)
                         for t in range(s, e)],
                        [dl_base + (fl_dn if flush_mask[t] else 0.0)
                         for t in range(s, e)],
                        overhead_bits=oh, snapshot_mask=seg_eval)
            history += [
                {"round": int(s + i) + 1, "acc": float(accs[i]),
                 "cum_bits": cum_bits, "bpp_so_far": bpp}
                for i, (cum_bits, bpp) in zip(np.nonzero(seg_eval)[0], snaps)]
            if checkpoint_dir and (e in bounds or e == rounds):
                th_c, thh_c, us_c, ds_c = carry
                self._save_state(checkpoint_dir, e, th_c, thh_c, us_c, ds_c,
                                 meter, history, cfg_blob)
            s = e
        theta, theta_hat = carry[0], carry[1]
        return self._result(history, meter, theta, theta_hat)

    @staticmethod
    def _result(history, meter, theta, theta_hat) -> Dict[str, Any]:
        return {"history": history, "meter": meter.summary(),
                "theta": theta, "theta_hat": theta_hat,
                "final_acc": history[-1]["acc"] if history else float("nan"),
                "max_acc": max(h["acc"] for h in history)
                if history else float("nan")}


def run_spec(task, spec: EngineSpec, shards: Dataset,
             theta0: Optional[jax.Array] = None, *, rounds: int,
             seed: int = 0, eval_every: int = 1, mode: str = "auto",
             cohort_rng: str = "numpy", **kwargs) -> Dict[str, Any]:
    """Convenience one-shot: build an engine and run it."""
    return FLEngine(task, spec).run(shards, theta0, rounds=rounds, seed=seed,
                                    eval_every=eval_every, mode=mode,
                                    cohort_rng=cohort_rng, **kwargs)
