"""Composable communication channels for the FL engine (cf. DESIGN.md).

BICompFL's central observation is that uplink and downlink are *both*
compression channels whose costs interact.  This module makes each direction
a first-class object: a :class:`Channel` encodes what one party sends, what
the other party reconstructs, and **how many bits crossed the wire** -- the
bit accounting lives in the channel, not in the training loop.

Functional core
---------------
Every channel is a *pure* function over an explicit state pytree, so the
engine can run the whole multi-round loop as one ``jax.lax.scan`` (the
device-resident fused path, cf. ``engine.FLEngine``):

Uplink channels implement::

    step_up(ctx, state, payload, priors) -> (server_side_estimates, bits, state)

where ``payload`` is the per-active-client message source -- Bernoulli
posteriors ``q`` for the probabilistic-mask path, weight deltas for
conventional FL -- and ``priors`` are the clients' current global-model
estimates (the MRC prior; ignored by the non-stochastic compressors).

Downlink channels implement::

    step_down(ctx, state, update, theta, theta_hat) -> (DownlinkResult, state)

receiving the aggregator's proposed :class:`ServerUpdate` and returning the
*final* server model, the new per-client estimates and the downlink bits.
The downlink owns the final model update because some schemes (sign-EF a la
DoubleSqueeze) have the server itself step with the *compressed* aggregate.

State is any pytree: ``()`` for stateless channels, the error-feedback
memory array for the EF compressors.  ``init_up_state(n, d)`` /
``init_down_state(n, d)`` build the initial state;
``flush_step(state, n, d) -> (residual, bits, state)`` implements the
periodic error-reset of CSER / LIEC.

Bits contract
-------------
``bits`` return values are computed from static shapes and the round's
:class:`BlockPlan`.  Under a *static* plan that makes them plain Python
floats, which lets the fused engine book communication host-side with zero
device syncs.  Under a bucketed adaptive plan (built on device inside the
fused scan body) ``plan.billable`` is a **traced** block count, so ``bits``
becomes a traced f32 scalar; the engine then carries per-round bits through
the scan outputs and books them into the BitMeter after the run.  Channels
must always bill ``plan.billable`` (never ``plan.n_blocks``, which is only
the static segment *capacity*) and must keep the bits expression otherwise
shape-derived, so both representations stay exact.

Wire hooks
----------
The wire audit (``FLEngine.run(wire="audit")``, cf. repro.wire) runs the
same explicit-state protocol with each stage serialized to frames:

    encode_up(ctx, state, payload, priors) -> (estimates, bits, state, msgs)
    decode_up(ctx, msgs, priors) -> estimates
    encode_down(ctx, state, update, theta, theta_hat, up_msgs)
        -> (DownlinkResult, state, msgs)
    decode_down(ctx, msgs, theta, theta_hat, env) -> DownlinkResult
    encode_flush_up(state, n, d) -> (residual, bits, state, msgs)

The encoders return exactly what ``step_up`` / ``step_down`` /
``flush_step`` return, plus the frames; the decoders rebuild the values
from the frames alone, and the engine lets the decoded values drive the
round.  There is one protocol: a channel holds no state of its own, so a
spec may be run any number of times.

Key-derivation tags reproduce the seed loops exactly, so the engine is
bit-for-bit compatible with the seed's loops (see
tests/test_engine_parity.py).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, NamedTuple, Optional, Protocol, Tuple, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import mrc
from repro.core.bernoulli import clip01
from repro.core.blocks import BlockPlan  # noqa: F401  (re-export: the plan
                                         # travels with the channel API)
from repro.core.quantizers import (FLOAT_BITS, sign_compress, topk_bits,
                                   topk_compress)
from repro.wire import (DIR_DOWN, DIR_FLUSH_UP, DIR_UP, SERVER, BitReader,
                        BitWriter, Message)
from repro.wire import codecs as wcodecs

# ---------------------------------------------------------------------------
# Key-derivation tags (shared-randomness schedule, identical to the seed).
# ---------------------------------------------------------------------------

TAG_TRAIN = 1          # per-round local-training keys
TAG_UL_SELECT = 2      # uplink Gumbel selection stream
TAG_DL_SHARED = 3      # downlink candidate stream
TAG_DL_SELECT_COMMON = 4   # downlink selection, common (GR-Reconst)
TAG_DL_SELECT_PRIVATE = 5  # downlink selection, per-client (PR variants)
TAG_COHORT = 6         # jax-native cohort sampling (engine, cohort_rng="jax")

# State pytree of a stateless channel: no leaves, trivially scan-carriable.
EMPTY_STATE: Tuple = ()


def pin(token, x):
    """Pin ``x``'s rounding against re-fusion inside one compiled program.

    The host loop materialises each stage's output between separately
    compiled dispatches; inside the engine's fused scan XLA instead fuses
    values into their consumers and LLVM FMA-contracts chains like
    ``theta - lr * mean(...)`` into a single rounding, breaking bit-parity
    with the host path.  ``optimization_barrier`` is deleted by the CPU
    pipeline and a select on a runtime predicate gets *sunk through* the
    arithmetic, so the robust pin routes the value through integer space:
    ``bitcast_f32->i32 -> add(token) -> bitcast_i32->f32`` where ``token``
    is a *traced* int32 zero (``RoundContext.pin_token``, fed from the scan
    xs so nothing can constant-fold it).  Adding integer zero is the exact
    identity on the bit pattern, and no floating-point rewrite crosses an
    integer op -- the f32 value is forced to its rounded form before any
    consumer sees it.  On the host path ``token`` is None and this is a
    no-op.  Only float32 leaves are touched; other dtypes are exact anyway.
    """
    if token is None:
        return x

    def _pin(v):
        v = jnp.asarray(v)
        if v.dtype != jnp.float32:
            return v
        bits = jax.lax.bitcast_convert_type(v, jnp.int32)
        return jax.lax.bitcast_convert_type(bits + token, jnp.float32)

    return jax.tree.map(_pin, x)


def _vfold(key: jax.Array, ids: jax.Array) -> jax.Array:
    """fold_in(key, i) for every client id i -> stacked keys."""
    return jax.vmap(lambda i: jax.random.fold_in(key, i))(ids)


def _vclient_keys(kt: jax.Array, ids: jax.Array) -> jax.Array:
    """Per-client private shared randomness, vmapped over ids."""
    return jax.vmap(lambda i: mrc.client_key(kt, i))(ids)


# ---------------------------------------------------------------------------
# Block helpers.  Pad value 0.5 for BOTH q and p => padded entries have zero
# KL and never influence the selected index.  Batched over leading dims.
# ---------------------------------------------------------------------------


def to_blocks(v: jax.Array, size: int) -> jax.Array:
    d = v.shape[-1]
    b = -(-d // size)
    pad = b * size - d
    if pad:
        v = jnp.concatenate([v, jnp.full(v.shape[:-1] + (pad,), 0.5, v.dtype)], axis=-1)
    return v.reshape(v.shape[:-1] + (b, size))


def from_blocks(m: jax.Array, d: int) -> jax.Array:
    return m.reshape(m.shape[:-2] + (-1,))[..., :d]


# ---------------------------------------------------------------------------
# Round context / server update.
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class RoundContext:
    """Everything a channel may need about the current global round.

    In the fused engine path ``t``, ``key`` and ``active`` are traced scan
    values (``active`` a jnp int vector); channels must only use them in
    traceable positions.  Cohort *size* stays static either way.
    """

    t: Any
    key: jax.Array        # kt = mrc.round_key(base, t) -- shared randomness
    n_clients: int
    d: int
    active: Any           # sorted global ids of the participating cohort
    plan: Optional[BlockPlan] = None
    pin_token: Any = None  # traced int32 zero in the fused path (cf. pin)
    # Aggregation weights over cohort positions under injected faults
    # (repro.fl.faults): 0.0 for dropped / straggling / lost-uplink
    # clients, 1.0 for contributors.  None on fault-free rounds, keeping
    # every aggregator expression bit-identical to the no-faults engine.
    up_weight: Any = None

    @property
    def n_active(self) -> int:
        return len(self.active)

    @property
    def active_ids(self) -> jax.Array:
        return jnp.asarray(self.active, dtype=jnp.int32)


@dataclass(frozen=True)
class ServerUpdate:
    """Aggregator output: the proposed next server model.

    ``delta`` carries the aggregate update direction for delta-space schemes
    (``theta = theta_prev - lr * delta``); it is None for model-space schemes
    (BiCompFL) whose aggregate *is* the new model.
    """

    theta: jax.Array
    delta: Optional[jax.Array] = None
    lr: float = 1.0


class DownlinkResult(NamedTuple):
    theta: jax.Array      # final server model after the downlink
    theta_hat: jax.Array  # (n_clients, d) client estimates
    bits: float


@dataclass(frozen=True)
class WireEnv:
    """Decoder-side context for ``decode_down`` (cf. repro.wire).

    Everything here is information the *receiving* party legitimately holds:
    its own uplink transmission (``up_msgs``, for index-relay downlinks),
    the shared uplink/aggregator definitions, the round's priors, and --
    server-side only -- the aggregator's proposed :class:`ServerUpdate`
    (used where the downlink result's ``theta`` never crosses the wire
    because it stays on the federator).
    """

    uplink: Any
    aggregator: Any
    priors: Any
    up_msgs: Any
    update: ServerUpdate


def _wire_msg(direction: int, sender: int, recipient: int,
              w: BitWriter) -> Message:
    """Seal a finished payload writer into an (unstamped) frame."""
    return Message(direction=direction, sender=int(sender),
                   recipient=int(recipient), payload=w.getvalue(),
                   payload_bits=w.bits_written)


def _wire_reader(m: Message) -> BitReader:
    return BitReader(m.payload, m.payload_bits)


@runtime_checkable
class UplinkChannel(Protocol):
    def init_up_state(self, n: int, d: int): ...

    def step_up(self, ctx: RoundContext, state, payload: jax.Array,
                priors: jax.Array) -> Tuple[jax.Array, float, Any]: ...

    def flush_step(self, state, n: int, d: int): ...

    def encode_up(self, ctx: RoundContext, state, payload: jax.Array,
                  priors: jax.Array): ...

    def decode_up(self, ctx: RoundContext, msgs, priors: jax.Array): ...

    def encode_flush_up(self, state, n: int, d: int): ...


@runtime_checkable
class DownlinkChannel(Protocol):
    broadcast_shareable: bool

    def init_down_state(self, n: int, d: int): ...

    def step_down(self, ctx: RoundContext, state, update: ServerUpdate,
                  theta: jax.Array,
                  theta_hat: jax.Array) -> Tuple[DownlinkResult, Any]: ...

    def flush_step(self, state, n: int, d: int): ...

    def encode_down(self, ctx: RoundContext, state, update: ServerUpdate,
                    theta: jax.Array, theta_hat: jax.Array, up_msgs): ...

    def decode_down(self, ctx: RoundContext, msgs, theta: jax.Array,
                    theta_hat: jax.Array, env: WireEnv) -> DownlinkResult: ...


# ---------------------------------------------------------------------------
# Uplink EF sync on the wire, and the memoryless channels' trivial state.
# ---------------------------------------------------------------------------


class DenseFlushUp:
    """``encode_flush_up`` for every uplink, over its own ``flush_step``.

    Wherever ``flush_step`` bills bits, each client uploads its state row
    as one dense f32 ``DIR_FLUSH_UP`` frame: its error-feedback row, or a
    zero row for a channel without memory.  An uplink whose flush bills
    nothing (the MRC channels) writes no frame.
    """

    def encode_flush_up(self, state, n: int, d: int):
        r, bits, new_state = self.flush_step(state, n, d)
        if not bits:
            return r, bits, new_state, []
        if bits != n * d * FLOAT_BITS:
            raise NotImplementedError(
                f"uplink flush books {bits} bits; the wire layer only knows "
                f"the dense sync protocol ({n * d * FLOAT_BITS} bits)")
        rows = np.asarray(state) if jax.tree.leaves(state) \
            else np.zeros((n, d), np.float32)
        msgs = []
        for cid in range(n):
            w = BitWriter()
            wcodecs.put_dense(w, rows[cid])
            msgs.append(_wire_msg(DIR_FLUSH_UP, cid, SERVER, w))
        return r, bits, new_state, msgs


def decode_flush_up(msgs, n: int, d: int) -> jax.Array:
    """The server's view of an uplink sync: the mean of the clients' rows."""
    rows = [wcodecs.get_dense(_wire_reader(m), d) for m in msgs]
    return jnp.mean(jnp.asarray(np.stack(rows)), axis=0)


class StatelessUplink(DenseFlushUp):
    """Trivial state for uplinks without memory."""

    def init_up_state(self, n: int, d: int):
        return EMPTY_STATE

    def flush_step(self, state, n: int, d: int):
        return 0.0, 0.0, state


class StatelessDownlink:
    """Trivial state for downlinks without memory."""

    # Downlink audience: "all" (every client holds an estimate of the
    # broadcast) or "active" (client-specific payloads for the cohort
    # only).  The engine's fault booking scales per-recipient bits by it.
    downlink_recipients = "all"

    def init_down_state(self, n: int, d: int):
        return EMPTY_STATE

    def flush_step(self, state, n: int, d: int):
        return 0.0, 0.0, state


# ---------------------------------------------------------------------------
# MRC channels (the paper's C_mrc, fixed-size blocks / adaptive segments).
# ---------------------------------------------------------------------------


@dataclass
class MRCFixedChannel(StatelessUplink):
    """Uplink MRC over fixed-size blocks, vmapped across the cohort.

    ``shared=True`` (GR) lets every client draw candidates from the *common*
    round key; ``shared=False`` (PR) vmaps over per-client private keys.
    """

    n_is: int = 256
    n_samples: int = 1
    shared: bool = True
    chunk: int = 16
    logw_fn: Any = None

    def _transmit(self, ctx, payload, priors):
        """Shared core: returns (indices, q_hat, bits).  ``step_up`` drops
        the indices (dead code under the fused scan); the wire codec
        serializes them."""
        plan = ctx.plan
        kt = ctx.key
        qb = to_blocks(clip01(payload), plan.size)   # (n_act, B, S)
        pb = to_blocks(clip01(priors), plan.size)
        sels = _vfold(jax.random.fold_in(kt, TAG_UL_SELECT), ctx.active_ids)

        def one(skey, sel, q_i, p_i):
            return mrc.transmit_fixed(
                skey, sel, q_i, p_i, n_is=self.n_is, n_samples=self.n_samples,
                chunk=self.chunk, logw_fn=self.logw_fn)

        if self.shared:
            idxs, q_hat_b = jax.vmap(
                lambda sel, q, p: one(kt, sel, q, p))(sels, qb, pb)
        else:
            skeys = _vclient_keys(kt, ctx.active_ids)
            idxs, q_hat_b = jax.vmap(one)(skeys, sels, qb, pb)
        bits = ctx.n_active * self.n_samples * plan.billable * math.log2(self.n_is)
        return idxs, from_blocks(q_hat_b, ctx.d), bits

    def step_up(self, ctx, state, payload, priors):
        _, q_hat, bits = self._transmit(ctx, payload, priors)
        return q_hat, bits, state

    # -- wire codec --------------------------------------------------------

    def encode_up(self, ctx, state, payload, priors):
        idxs, q_hat, bits = self._transmit(ctx, payload, priors)
        idxs = np.asarray(idxs)  # (n_act, n_samples, B)
        msgs = []
        for j, cid in enumerate(np.asarray(ctx.active)):
            w = BitWriter()
            wcodecs.put_indices(w, idxs[j], self.n_is)
            msgs.append(_wire_msg(DIR_UP, cid, SERVER, w))
        return q_hat, bits, state, msgs

    def decode_up(self, ctx, msgs, priors):
        plan, kt = ctx.plan, ctx.key
        pb = to_blocks(clip01(priors), plan.size)
        shape = (self.n_samples, plan.n_blocks)
        idxs = []
        for m in msgs:
            r = _wire_reader(m)
            idxs.append(wcodecs.get_indices(r, shape, self.n_is))
            r.expect_exhausted()
        idxs = jnp.asarray(np.stack(idxs))
        if self.shared:
            q_hat_b = jax.vmap(lambda idx, p: mrc.receive_fixed(
                kt, idx, p, n_is=self.n_is))(idxs, pb)
        else:
            skeys = _vclient_keys(kt, ctx.active_ids)
            q_hat_b = jax.vmap(lambda k, idx, p: mrc.receive_fixed(
                k, idx, p, n_is=self.n_is))(skeys, idxs, pb)
        return from_blocks(q_hat_b, ctx.d)


@dataclass
class MRCAdaptiveChannel(StatelessUplink):
    """Uplink MRC over variable-size segments (Isik et al. 2024 allocation)."""

    n_is: int = 256
    n_samples: int = 1
    shared: bool = True
    seg_logw_fn: Any = None

    def _transmit(self, ctx, payload, priors):
        plan = ctx.plan
        kt = ctx.key
        seg = jnp.asarray(plan.seg_ids)
        sels = _vfold(jax.random.fold_in(kt, TAG_UL_SELECT), ctx.active_ids)

        def one(skey, sel, q_i, p_i):
            return mrc.transmit_segments(
                skey, sel, q_i, clip01(p_i), seg, n_is=self.n_is,
                n_seg=plan.n_blocks, n_samples=self.n_samples,
                seg_logw_fn=self.seg_logw_fn)

        q = clip01(payload)
        if self.shared:
            idxs, q_hat = jax.vmap(
                lambda sel, q_i, p: one(kt, sel, q_i, p))(sels, q, priors)
        else:
            skeys = _vclient_keys(kt, ctx.active_ids)
            idxs, q_hat = jax.vmap(one)(skeys, sels, q, priors)
        bits = ctx.n_active * self.n_samples * plan.billable * math.log2(self.n_is)
        return idxs, q_hat, bits

    def step_up(self, ctx, state, payload, priors):
        _, q_hat, bits = self._transmit(ctx, payload, priors)
        return q_hat, bits, state

    # -- wire codec --------------------------------------------------------

    def encode_up(self, ctx, state, payload, priors):
        idxs, q_hat, bits = self._transmit(ctx, payload, priors)
        idxs = np.asarray(idxs)  # (n_act, n_samples, n_seg)
        msgs = []
        for j, cid in enumerate(np.asarray(ctx.active)):
            w = BitWriter()
            wcodecs.put_indices(w, idxs[j], self.n_is)
            msgs.append(_wire_msg(DIR_UP, cid, SERVER, w))
        return q_hat, bits, state, msgs

    def decode_up(self, ctx, msgs, priors):
        plan, kt = ctx.plan, ctx.key
        seg = jnp.asarray(plan.seg_ids)
        shape = (self.n_samples, plan.n_blocks)
        idxs = []
        for m in msgs:
            r = _wire_reader(m)
            idxs.append(wcodecs.get_indices(r, shape, self.n_is))
            r.expect_exhausted()
        idxs = jnp.asarray(np.stack(idxs))
        if self.shared:
            q_hat = jax.vmap(lambda idx, p: mrc.receive_segments(
                kt, idx, clip01(p), seg, n_is=self.n_is))(idxs, priors)
        else:
            skeys = _vclient_keys(kt, ctx.active_ids)
            q_hat = jax.vmap(lambda k, idx, p: mrc.receive_segments(
                k, idx, clip01(p), seg, n_is=self.n_is))(skeys, idxs, priors)
        return q_hat


@dataclass
class QuantizedMRCUplink(StatelessUplink):
    """Conventional-FL uplink: stochastic sign -> MRC vs the Ber(1/2) prior.

    Each client maps its delta to a Bernoulli posterior q = sigmoid(delta/K)
    with per-client temperature K = mean|delta| (32-bit side information),
    conveys ``n_samples`` MRC samples against the uninformative prior, and
    the server reconstructs the direction (2*q_hat - 1) * K.
    """

    n_is: int = 256
    n_samples: int = 1
    chunk: int = 16
    logw_fn: Any = None
    side_info_bits: float = FLOAT_BITS

    def _transmit(self, ctx, payload, priors):
        plan = ctx.plan
        kt = ctx.key
        d = ctx.d
        p_blocks = jnp.full((plan.n_blocks, plan.size), 0.5, jnp.float32)
        sels = _vfold(jax.random.fold_in(kt, TAG_UL_SELECT), ctx.active_ids)

        # Each K fans into the posterior and the reconstruction rescale; pin
        # the vector so the fused engine rounds like the host loop.
        Ks = pin(ctx.pin_token,
                 jax.vmap(lambda delta: jnp.mean(jnp.abs(delta)) + 1e-12)(payload))

        def one(sel, delta, K):
            q_i = clip01(jax.nn.sigmoid(delta / K))
            idx, q_hat_b = mrc.transmit_fixed(
                kt, sel, to_blocks(q_i, plan.size), p_blocks, n_is=self.n_is,
                n_samples=self.n_samples, chunk=self.chunk, logw_fn=self.logw_fn)
            return idx, (2.0 * from_blocks(q_hat_b, d) - 1.0) * K

        idxs, g_hat = jax.vmap(one)(sels, payload, Ks)
        bits = ctx.n_active * (self.n_samples * plan.billable * math.log2(self.n_is)
                               + self.side_info_bits)
        return idxs, Ks, g_hat, bits

    def step_up(self, ctx, state, payload, priors):
        _, _, g_hat, bits = self._transmit(ctx, payload, priors)
        return g_hat, bits, state

    # -- wire codec --------------------------------------------------------
    # Payload per client: the f32 temperature K (the booked 32-bit side
    # information), then the MRC index stream.

    def encode_up(self, ctx, state, payload, priors):
        if self.side_info_bits != FLOAT_BITS:
            raise NotImplementedError(
                "wire codec encodes K as one f32; side_info_bits="
                f"{self.side_info_bits} cannot be serialized at that rate")
        idxs, Ks, g_hat, bits = self._transmit(ctx, payload, priors)
        idxs, Ks = np.asarray(idxs), np.asarray(Ks)
        msgs = []
        for j, cid in enumerate(np.asarray(ctx.active)):
            w = BitWriter()
            w.write_f32(Ks[j])
            wcodecs.put_indices(w, idxs[j], self.n_is)
            msgs.append(_wire_msg(DIR_UP, cid, SERVER, w))
        return g_hat, bits, state, msgs

    def decode_up(self, ctx, msgs, priors):
        plan, kt, d = ctx.plan, ctx.key, ctx.d
        p_blocks = jnp.full((plan.n_blocks, plan.size), 0.5, jnp.float32)
        shape = (self.n_samples, plan.n_blocks)
        Ks, idxs = [], []
        for m in msgs:
            r = _wire_reader(m)
            Ks.append(r.read_f32())
            idxs.append(wcodecs.get_indices(r, shape, self.n_is))
            r.expect_exhausted()
        Ks = jnp.asarray(np.stack(Ks))
        idxs = jnp.asarray(np.stack(idxs))

        def one(idx, K):
            q_hat_b = mrc.receive_fixed(kt, idx, p_blocks, n_is=self.n_is)
            return (2.0 * from_blocks(q_hat_b, d) - 1.0) * K

        return jax.vmap(one)(idxs, Ks)


# ---------------------------------------------------------------------------
# BiCompFL downlinks.
# ---------------------------------------------------------------------------


@dataclass
class IndexRelayDownlink(StatelessDownlink):
    """GR downlink: relay the other clients' uplink indices.

    With common candidates every client reconstructs the identical global
    model, so no recomputation is needed -- only the bits are booked:
    each client receives the (n-1) other clients' index streams (plus
    optional per-client side information, e.g. the CFL temperatures).
    """

    n_is: int = 256
    n_samples: int = 1           # relayed samples per client (n_UL)
    side_info_bits: float = 0.0
    broadcast_shareable: bool = True

    def step_down(self, ctx, state, update, theta, theta_hat):
        n = ctx.n_clients
        th = update.theta
        bits = n * (n - 1) * (self.n_samples * ctx.plan.billable
                              * math.log2(self.n_is) + self.side_info_bits)
        return DownlinkResult(th, jnp.tile(th[None], (n, 1)), bits), state

    # -- wire codec --------------------------------------------------------
    # The relay's payloads ARE the uplink payloads: each client receives the
    # (n-1) other clients' frames verbatim (for CFL those frames already
    # carry the K side information the channel books).

    def encode_down(self, ctx, state, update, theta, theta_hat, up_msgs):
        res, state = self.step_down(ctx, state, update, theta, theta_hat)
        if len(up_msgs) != ctx.n_clients:
            raise ValueError("index relay needs every client's uplink frame")
        msgs = []
        for rcpt in np.asarray(ctx.active):
            for m in up_msgs:
                if m.sender == int(rcpt):
                    continue
                msgs.append(Message(direction=DIR_DOWN, sender=m.sender,
                                    recipient=int(rcpt), payload=m.payload,
                                    payload_bits=m.payload_bits))
        return res, state, msgs

    def decode_down(self, ctx, msgs, theta, theta_hat, env: WireEnv):
        """Reconstruct through the *first* client's receive path: its own
        transmission plus the n-1 relays, decoded with the shared uplink
        codec and re-aggregated -- with common candidates this must land on
        exactly the server's model."""
        n = ctx.n_clients
        ref = int(np.asarray(ctx.active)[0])
        by_sender = {m.sender: m for m in msgs if m.recipient == ref}
        ordered = []
        for cid in np.asarray(ctx.active):
            if int(cid) == ref:
                own = [m for m in env.up_msgs if m.sender == ref]
                ordered.append(own[0])
            else:
                ordered.append(by_sender[int(cid)])
        up_out = env.uplink.decode_up(ctx, ordered, env.priors)
        th = env.aggregator(ctx, theta, up_out).theta
        bits = n * (n - 1) * (self.n_samples * ctx.plan.billable
                              * math.log2(self.n_is) + self.side_info_bits)
        return DownlinkResult(th, jnp.tile(th[None], (n, 1)), bits)


@dataclass
class MRCBroadcastDownlink(StatelessDownlink):
    """GR-Reconst downlink: one MRC re-transmission against the common prior;
    all clients share candidates and end with the same (noisy) estimate."""

    n_is: int = 256
    n_samples: int = 1           # n_DL
    chunk: int = 16
    logw_fn: Any = None
    seg_logw_fn: Any = None
    broadcast_shareable: bool = True

    def _transmit(self, ctx, update, theta_hat):
        kt, plan, d = ctx.key, ctx.plan, ctx.d
        skey = jax.random.fold_in(kt, TAG_DL_SHARED)
        sel = jax.random.fold_in(kt, TAG_DL_SELECT_COMMON)
        p_common = clip01(theta_hat[0])
        tgt = update.theta
        if plan.adaptive:
            idxs, est = mrc.transmit_segments(
                skey, sel, tgt, p_common, jnp.asarray(plan.seg_ids),
                n_is=self.n_is, n_seg=plan.n_blocks, n_samples=self.n_samples,
                seg_logw_fn=self.seg_logw_fn)
        else:
            idxs, est_b = mrc.transmit_fixed(
                skey, sel, to_blocks(tgt, plan.size), to_blocks(p_common, plan.size),
                n_is=self.n_is, n_samples=self.n_samples, chunk=self.chunk,
                logw_fn=self.logw_fn)
            est = from_blocks(est_b, d)
        bits = ctx.n_clients * self.n_samples * plan.billable * math.log2(self.n_is)
        return idxs, est, bits

    def step_down(self, ctx, state, update, theta, theta_hat):
        _, est, bits = self._transmit(ctx, update, theta_hat)
        return DownlinkResult(
            update.theta, jnp.tile(clip01(est)[None], (ctx.n_clients, 1)),
            bits), state

    # -- wire codec --------------------------------------------------------
    # One index stream, broadcast: n frames with identical payload (the
    # channel bills per client, so the stream totals match by construction).

    def encode_down(self, ctx, state, update, theta, theta_hat, up_msgs):
        idxs, est, bits = self._transmit(ctx, update, theta_hat)
        w = BitWriter()
        wcodecs.put_indices(w, np.asarray(idxs), self.n_is)
        payload, nbits = w.getvalue(), w.bits_written
        msgs = [Message(direction=DIR_DOWN, sender=SERVER, recipient=int(cid),
                        payload=payload, payload_bits=nbits)
                for cid in np.asarray(ctx.active)]
        res = DownlinkResult(
            update.theta, jnp.tile(clip01(est)[None], (ctx.n_clients, 1)),
            bits)
        return res, state, msgs

    def decode_down(self, ctx, msgs, theta, theta_hat, env: WireEnv):
        kt, plan, d = ctx.key, ctx.plan, ctx.d
        skey = jax.random.fold_in(kt, TAG_DL_SHARED)
        r = _wire_reader(msgs[0])
        idxs = wcodecs.get_indices(
            r, (self.n_samples, plan.n_blocks), self.n_is)
        r.expect_exhausted()
        idxs = jnp.asarray(idxs)
        p_common = clip01(theta_hat[0])
        if plan.adaptive:
            est = mrc.receive_segments(skey, idxs, p_common,
                                       jnp.asarray(plan.seg_ids),
                                       n_is=self.n_is)
        else:
            est_b = mrc.receive_fixed(skey, idxs,
                                      to_blocks(p_common, plan.size),
                                      n_is=self.n_is)
            est = from_blocks(est_b, d)
        bits = ctx.n_clients * self.n_samples * plan.billable * math.log2(self.n_is)
        return DownlinkResult(
            env.update.theta,
            jnp.tile(clip01(est)[None], (ctx.n_clients, 1)), bits)


@dataclass
class MRCPrivateDownlink(StatelessDownlink):
    """PR downlink: per-client MRC against each client's own prior, vmapped
    over per-client private keys.  Under partial participation only the
    active cohort receives the downlink; stragglers keep stale estimates."""

    n_is: int = 256
    n_samples: int = 1           # n_DL
    chunk: int = 16
    logw_fn: Any = None
    seg_logw_fn: Any = None
    broadcast_shareable: bool = False
    downlink_recipients = "active"  # client-specific payloads, cohort only

    def _transmit(self, ctx, update, theta_hat):
        kt, plan, d = ctx.key, ctx.plan, ctx.d
        ids = ctx.active_ids
        skeys = jax.vmap(lambda k: jax.random.fold_in(k, TAG_DL_SHARED))(
            _vclient_keys(kt, ids))
        sels = _vfold(jax.random.fold_in(kt, TAG_DL_SELECT_PRIVATE), ids)
        priors = clip01(theta_hat[ids])
        tgt = update.theta
        if plan.adaptive:
            seg = jnp.asarray(plan.seg_ids)

            def one(skey, sel, p_i):
                return mrc.transmit_segments(
                    skey, sel, tgt, p_i, seg, n_is=self.n_is,
                    n_seg=plan.n_blocks, n_samples=self.n_samples,
                    seg_logw_fn=self.seg_logw_fn)
        else:
            tb = to_blocks(tgt, plan.size)

            def one(skey, sel, p_i):
                idx, est_b = mrc.transmit_fixed(
                    skey, sel, tb, to_blocks(p_i, plan.size), n_is=self.n_is,
                    n_samples=self.n_samples, chunk=self.chunk, logw_fn=self.logw_fn)
                return idx, from_blocks(est_b, d)

        idxs, est = jax.vmap(one)(skeys, sels, priors)
        bits = ctx.n_active * self.n_samples * plan.billable * math.log2(self.n_is)
        return idxs, est, bits

    def step_down(self, ctx, state, update, theta, theta_hat):
        _, est, bits = self._transmit(ctx, update, theta_hat)
        theta_hat = theta_hat.at[ctx.active_ids].set(clip01(est))
        return DownlinkResult(update.theta, theta_hat, bits), state

    # -- wire codec --------------------------------------------------------

    def encode_down(self, ctx, state, update, theta, theta_hat, up_msgs):
        idxs, est, bits = self._transmit(ctx, update, theta_hat)
        idxs = np.asarray(idxs)  # (n_act, n_samples, B)
        msgs = []
        for j, cid in enumerate(np.asarray(ctx.active)):
            w = BitWriter()
            wcodecs.put_indices(w, idxs[j], self.n_is)
            msgs.append(_wire_msg(DIR_DOWN, SERVER, cid, w))
        new_hat = theta_hat.at[ctx.active_ids].set(clip01(est))
        return DownlinkResult(update.theta, new_hat, bits), state, msgs

    def decode_down(self, ctx, msgs, theta, theta_hat, env: WireEnv):
        kt, plan, d = ctx.key, ctx.plan, ctx.d
        ids = ctx.active_ids
        skeys = jax.vmap(lambda k: jax.random.fold_in(k, TAG_DL_SHARED))(
            _vclient_keys(kt, ids))
        priors = clip01(theta_hat[ids])
        shape = (self.n_samples, plan.n_blocks)
        idxs = []
        for m in msgs:
            r = _wire_reader(m)
            idxs.append(wcodecs.get_indices(r, shape, self.n_is))
            r.expect_exhausted()
        idxs = jnp.asarray(np.stack(idxs))
        if plan.adaptive:
            seg = jnp.asarray(plan.seg_ids)
            est = jax.vmap(lambda k, idx, p: mrc.receive_segments(
                k, idx, p, seg, n_is=self.n_is))(skeys, idxs, priors)
        else:
            est = jax.vmap(lambda k, idx, p: from_blocks(mrc.receive_fixed(
                k, idx, to_blocks(p, plan.size), n_is=self.n_is), d))(
                    skeys, idxs, priors)
        new_hat = theta_hat.at[ids].set(clip01(est))
        bits = ctx.n_active * self.n_samples * plan.billable * math.log2(self.n_is)
        return DownlinkResult(env.update.theta, new_hat, bits)


@dataclass
class SplitBlockDownlink(StatelessDownlink):
    """PR-SplitDL: each client receives MRC only for a disjoint 1/n of the
    blocks (downlink cost / n); the rest of its estimate stays as-is.

    Clients own interleaved block subsets arange(i, B, n).  The per-client
    subsets are ragged when B % n != 0, so they are padded to the common
    maximum with one sentinel block whose result is discarded -- this keeps
    the whole downlink a single vmapped transmission.  Fixed blocks only.
    """

    n_is: int = 256
    n_samples: int = 1           # n_DL
    chunk: int = 16
    logw_fn: Any = None
    broadcast_shareable: bool = False

    @staticmethod
    def _ownership(n: int, n_blocks: int):
        """Padded interleaved block-ownership table and its sentinel row."""
        max_len = -(-n_blocks // n)
        own_pad = np.full((n, max_len), n_blocks, np.int32)
        for i in range(n):
            own = np.arange(i, n_blocks, n, dtype=np.int32)
            own_pad[i, :len(own)] = own
        return jnp.asarray(own_pad), max_len

    def _transmit(self, ctx, update, theta_hat):
        kt, plan, d = ctx.key, ctx.plan, ctx.d
        if plan.adaptive:
            raise NotImplementedError("SplitDL is defined on fixed blocks")
        n, size, n_blocks = ctx.n_clients, plan.size, plan.n_blocks
        # Sentinel index n_blocks targets a dummy row.
        own_pad, max_len = self._ownership(n, n_blocks)

        tb = to_blocks(update.theta, size)                       # (B, S)
        dummy = jnp.full((1, size), 0.5, tb.dtype)
        tb_ext = jnp.concatenate([tb, dummy])
        hb_all = to_blocks(clip01(theta_hat), size)              # (n, B, S)
        ids = jnp.arange(n, dtype=jnp.int32)
        skeys = jax.vmap(lambda k: jax.random.fold_in(k, TAG_DL_SHARED))(
            _vclient_keys(kt, ids))
        sels = _vfold(jax.random.fold_in(kt, TAG_DL_SELECT_PRIVATE), ids)
        chunk = min(self.chunk, max_len)

        def one(skey, sel, hb_i, own_i):
            hb_ext = jnp.concatenate([hb_i, dummy])
            idx, est_b = mrc.transmit_fixed(
                skey, sel, tb_ext[own_i], hb_ext[own_i], n_is=self.n_is,
                n_samples=self.n_samples, chunk=chunk, logw_fn=self.logw_fn)
            hb_ext = hb_ext.at[own_i].set(clip01(est_b))
            return idx, from_blocks(hb_ext[:n_blocks], d)

        idxs, theta_hat = jax.vmap(one)(skeys, sels, hb_all, own_pad)
        bits = n * self.n_samples * max_len * math.log2(self.n_is)
        return idxs, theta_hat, bits

    def step_down(self, ctx, state, update, theta, theta_hat):
        _, theta_hat, bits = self._transmit(ctx, update, theta_hat)
        return DownlinkResult(update.theta, theta_hat, bits), state

    # -- wire codec --------------------------------------------------------
    # Per client: indices for its (padded) owned-block subset, sentinel
    # included -- the channel bills the padding, so the wire carries it.

    def encode_down(self, ctx, state, update, theta, theta_hat, up_msgs):
        idxs, new_hat, bits = self._transmit(ctx, update, theta_hat)
        idxs = np.asarray(idxs)  # (n, n_samples, max_len)
        msgs = []
        for j, cid in enumerate(np.asarray(ctx.active)):
            w = BitWriter()
            wcodecs.put_indices(w, idxs[j], self.n_is)
            msgs.append(_wire_msg(DIR_DOWN, SERVER, cid, w))
        return DownlinkResult(update.theta, new_hat, bits), state, msgs

    def decode_down(self, ctx, msgs, theta, theta_hat, env: WireEnv):
        kt, plan, d = ctx.key, ctx.plan, ctx.d
        n, size, n_blocks = ctx.n_clients, plan.size, plan.n_blocks
        own_pad, max_len = self._ownership(n, n_blocks)
        dummy = jnp.full((1, size), 0.5, jnp.float32)
        hb_all = to_blocks(clip01(theta_hat), size)
        ids = jnp.arange(n, dtype=jnp.int32)
        skeys = jax.vmap(lambda k: jax.random.fold_in(k, TAG_DL_SHARED))(
            _vclient_keys(kt, ids))
        shape = (self.n_samples, max_len)
        idxs = []
        for m in msgs:
            r = _wire_reader(m)
            idxs.append(wcodecs.get_indices(r, shape, self.n_is))
            r.expect_exhausted()
        idxs = jnp.asarray(np.stack(idxs))

        def one(skey, idx, hb_i, own_i):
            hb_ext = jnp.concatenate([hb_i, dummy])
            est_b = mrc.receive_fixed(skey, idx, hb_ext[own_i],
                                      n_is=self.n_is)
            hb_ext = hb_ext.at[own_i].set(clip01(est_b))
            return from_blocks(hb_ext[:n_blocks], d)

        new_hat = jax.vmap(one)(skeys, idxs, hb_all, own_pad)
        bits = n * self.n_samples * max_len * math.log2(self.n_is)
        return DownlinkResult(env.update.theta, new_hat, bits)


# ---------------------------------------------------------------------------
# Non-stochastic baseline channels.
# ---------------------------------------------------------------------------


@dataclass
class DenseChannel(StatelessUplink, StatelessDownlink):
    """Lossless 32-bit transmission; usable on either direction."""

    bits_per_value: float = FLOAT_BITS
    broadcast_shareable: bool = True

    def step_up(self, ctx, state, payload, priors):
        return payload, ctx.n_active * ctx.d * self.bits_per_value, state

    def step_down(self, ctx, state, update, theta, theta_hat):
        th = update.theta
        return DownlinkResult(th, jnp.tile(th[None], (ctx.n_clients, 1)),
                              ctx.n_clients * ctx.d * self.bits_per_value), state

    def flush_step(self, state, n, d):
        # Stateless: a periodic sync through a dense channel only costs bits.
        return 0.0, n * d * self.bits_per_value, state

    # -- wire codec: raw big-endian f32 vectors ----------------------------

    def _check_rate(self):
        if self.bits_per_value != FLOAT_BITS:
            raise NotImplementedError(
                f"dense wire codec is f32-only ({self.bits_per_value} "
                "bits/value requested)")

    def encode_up(self, ctx, state, payload, priors):
        self._check_rate()
        rows = np.asarray(payload)
        msgs = []
        for j, cid in enumerate(np.asarray(ctx.active)):
            w = BitWriter()
            wcodecs.put_dense(w, rows[j])
            msgs.append(_wire_msg(DIR_UP, cid, SERVER, w))
        return payload, ctx.n_active * ctx.d * self.bits_per_value, state, msgs

    def decode_up(self, ctx, msgs, priors):
        rows = []
        for m in msgs:
            r = _wire_reader(m)
            rows.append(wcodecs.get_dense(r, ctx.d))
            r.expect_exhausted()
        return jnp.asarray(np.stack(rows))

    def encode_down(self, ctx, state, update, theta, theta_hat, up_msgs):
        self._check_rate()
        res, state = self.step_down(ctx, state, update, theta, theta_hat)
        w = BitWriter()
        wcodecs.put_dense(w, np.asarray(update.theta))
        payload, nbits = w.getvalue(), w.bits_written
        msgs = [Message(direction=DIR_DOWN, sender=SERVER, recipient=int(cid),
                        payload=payload, payload_bits=nbits)
                for cid in range(ctx.n_clients)]
        return res, state, msgs

    def decode_down(self, ctx, msgs, theta, theta_hat, env: WireEnv):
        r = _wire_reader(msgs[0])
        th = jnp.asarray(wcodecs.get_dense(r, ctx.d))
        r.expect_exhausted()
        return DownlinkResult(th, jnp.tile(th[None], (ctx.n_clients, 1)),
                              ctx.n_clients * ctx.d * self.bits_per_value)



@dataclass
class SignEFChannel(DenseFlushUp):
    """Sign compression with error feedback; ``passes>1`` repeats compression
    on the residual (Neolithic's R-pass scheme, ~``passes`` bits/param).

    As an uplink it keeps per-client EF memory (n, d); as a downlink it
    keeps the server-side memory (d,) and steps server *and* clients with
    the compressed aggregate (DoubleSqueeze).
    """

    passes: int = 1
    broadcast_shareable: bool = True
    downlink_recipients = "all"

    def _compress(self, v):
        """Iterated sign compression, also yielding the per-pass wire
        payload: (scale, sign-bit vector) per pass.  The reconstruction
        ``sum_r scale_r * (+-1)`` is the compressed vector
        (``sign_compress`` is scale * where(v >= 0, 1, -1))."""
        comps = []
        c = None
        resid = v
        for _ in range(self.passes):
            scale = jnp.mean(jnp.abs(resid))
            sgn = resid >= 0
            step = sign_compress(resid)  # == scale * where(sgn, 1, -1)
            c = step if c is None else c + step
            resid = v - c
            comps.append((scale, sgn))
        return c, comps

    def _up(self, ctx, e, payload):
        """Shared uplink core: (compressed, bits, new memory, per-pass
        wire payload).  ``step_up`` drops the payload."""
        if ctx.n_active != ctx.n_clients:
            raise ValueError("error-feedback uplinks require full participation")
        acc = payload + e
        c, comps = jax.vmap(self._compress)(acc)
        bits = ctx.n_clients * self.passes * (ctx.d + FLOAT_BITS)
        return c, bits, acc - c, comps

    def _down(self, ctx, e, update, theta, theta_hat):
        """Shared downlink core: (result, new memory, per-pass payload)."""
        g = update.delta if update.delta is not None \
            else (theta - update.theta) / update.lr
        agg = g + e
        c_s, comps = self._compress(agg)
        bits = ctx.n_clients * self.passes * (ctx.d + FLOAT_BITS)
        return DownlinkResult(theta - update.lr * c_s,
                              theta_hat - update.lr * c_s[None, :],
                              bits), agg - c_s, comps

    # -- functional core --------------------------------------------------
    def init_up_state(self, n, d):
        return jnp.zeros((n, d), jnp.float32)

    def init_down_state(self, n, d):
        return jnp.zeros((d,), jnp.float32)

    def step_up(self, ctx, e, payload, priors):
        c, bits, e, _ = self._up(ctx, e, payload)
        return c, bits, e

    def step_down(self, ctx, e, update, theta, theta_hat):
        res, e, _ = self._down(ctx, e, update, theta, theta_hat)
        return res, e

    def flush_step(self, e, n, d):
        r = jnp.mean(e, axis=0) if e.ndim == 2 else e
        return r, n * d * FLOAT_BITS, jnp.zeros_like(e)

    # -- wire codec --------------------------------------------------------
    # Per client (uplink) / broadcast (downlink): ``passes`` records of one
    # f32 scale + a d-bit sign bitmap -- the booked passes * (d + 32).

    def _decode_compressed(self, r, d):
        c = None
        for _ in range(self.passes):
            scale, sgn = wcodecs.get_sign_pass(r, d)
            step = jnp.float32(scale) * jnp.where(jnp.asarray(sgn), 1.0, -1.0)
            c = step if c is None else c + step
        return c

    def encode_up(self, ctx, e, payload, priors):
        c, bits, e, comps = self._up(ctx, e, payload)
        msgs = []
        for j, cid in enumerate(np.asarray(ctx.active)):
            w = BitWriter()
            for scale, sgn in comps:
                wcodecs.put_sign_pass(w, np.asarray(scale)[j],
                                      np.asarray(sgn)[j])
            msgs.append(_wire_msg(DIR_UP, cid, SERVER, w))
        return c, bits, e, msgs

    def decode_up(self, ctx, msgs, priors):
        rows = []
        for m in msgs:
            r = _wire_reader(m)
            rows.append(self._decode_compressed(r, ctx.d))
            r.expect_exhausted()
        return jnp.stack(rows)

    def encode_down(self, ctx, e, update, theta, theta_hat, up_msgs):
        res, e, comps = self._down(ctx, e, update, theta, theta_hat)
        w = BitWriter()
        for scale, sgn in comps:
            wcodecs.put_sign_pass(w, np.asarray(scale), np.asarray(sgn))
        payload, nbits = w.getvalue(), w.bits_written
        msgs = [Message(direction=DIR_DOWN, sender=SERVER, recipient=int(cid),
                        payload=payload, payload_bits=nbits)
                for cid in range(ctx.n_clients)]
        return res, e, msgs

    def decode_down(self, ctx, msgs, theta, theta_hat, env: WireEnv):
        r = _wire_reader(msgs[0])
        c_s = self._decode_compressed(r, ctx.d)
        r.expect_exhausted()
        lr = env.update.lr
        bits = ctx.n_clients * self.passes * (ctx.d + FLOAT_BITS)
        return DownlinkResult(theta - lr * c_s,
                              theta_hat - lr * c_s[None, :], bits)


@dataclass
class TopKEFChannel(DenseFlushUp):
    """Top-k sparsification with error feedback (M3 uplink, k = d/n)."""

    k: int = 1

    def _up(self, ctx, e, payload):
        """Shared uplink core: (accumulator, compressed, bits)."""
        if ctx.n_active != ctx.n_clients:
            raise ValueError("error-feedback uplinks require full participation")
        acc = payload + e
        c = jax.vmap(lambda v: topk_compress(v, self.k))(acc)
        return acc, c, ctx.n_clients * topk_bits(ctx.d, self.k)

    # -- functional core --------------------------------------------------
    def init_up_state(self, n, d):
        return jnp.zeros((n, d), jnp.float32)

    def step_up(self, ctx, e, payload, priors):
        acc, c, bits = self._up(ctx, e, payload)
        return c, bits, acc - c

    def flush_step(self, e, n, d):
        return jnp.mean(e, axis=0), n * d * FLOAT_BITS, jnp.zeros_like(e)

    # -- wire codec --------------------------------------------------------
    # Per client: k records of (ceil(log2 d)-bit index, f32 value) -- the
    # booked topk_bits(d, k).

    def encode_up(self, ctx, e, payload, priors):
        acc, c, bits = self._up(ctx, e, payload)
        kk = min(self.k, ctx.d)
        _, idxs = jax.vmap(lambda v: jax.lax.top_k(jnp.abs(v), kk))(acc)
        vals = jnp.take_along_axis(acc, idxs, axis=1)
        msgs = []
        for j, cid in enumerate(np.asarray(ctx.active)):
            w = BitWriter()
            wcodecs.put_topk(w, np.asarray(idxs)[j], np.asarray(vals)[j],
                             ctx.d)
            msgs.append(_wire_msg(DIR_UP, cid, SERVER, w))
        return c, bits, acc - c, msgs

    def decode_up(self, ctx, msgs, priors):
        kk = min(self.k, ctx.d)
        rows = []
        for m in msgs:
            r = _wire_reader(m)
            idx, vals = wcodecs.get_topk(r, kk, ctx.d)
            r.expect_exhausted()
            rows.append(jnp.zeros(ctx.d, jnp.float32)
                        .at[jnp.asarray(idx)].set(jnp.asarray(vals)))
        return jnp.stack(rows)


@dataclass
class SliceDownlink(StatelessDownlink):
    """M3 downlink: each client receives a disjoint dense 1/n model slice;
    client estimates diverge (no broadcast saving possible).

    ``k`` (slice width) defaults to d/n at runtime; pass it explicitly to
    keep it consistent with a paired Top-k uplink budget."""

    k: Optional[int] = None
    broadcast_shareable: bool = False

    def step_down(self, ctx, state, update, theta, theta_hat):
        n, d = ctx.n_clients, ctx.d
        th = update.theta
        k = self.k if self.k is not None else max(d // n, 1)
        new_hat = []
        for i in range(n):
            lo = i * k
            hi = d if i == n - 1 else min((i + 1) * k, d)
            new_hat.append(theta_hat[i].at[lo:hi].set(th[lo:hi]))
        return DownlinkResult(th, jnp.stack(new_hat),
                              n * (d / n) * FLOAT_BITS), state

    # -- wire codec --------------------------------------------------------
    # Client i's message carries its dense f32 slice [i*k, hi); the slices
    # tile [0, d) so the stream totals d * 32 bits == the booked
    # n * (d/n) * 32 up to float round-off (cf. RECONCILE_REL_TOL).

    def _bounds(self, n, d):
        k = self.k if self.k is not None else max(d // n, 1)
        out = []
        for i in range(n):
            lo = i * k
            hi = d if i == n - 1 else min((i + 1) * k, d)
            out.append((lo, hi))
        return out

    def encode_down(self, ctx, state, update, theta, theta_hat, up_msgs):
        res, state = self.step_down(ctx, state, update, theta, theta_hat)
        th = np.asarray(res.theta)
        msgs = []
        for cid, (lo, hi) in enumerate(self._bounds(ctx.n_clients, ctx.d)):
            w = BitWriter()
            wcodecs.put_dense(w, th[lo:hi])
            msgs.append(_wire_msg(DIR_DOWN, SERVER, cid, w))
        return res, state, msgs

    def decode_down(self, ctx, msgs, theta, theta_hat, env: WireEnv):
        n, d = ctx.n_clients, ctx.d
        by_recipient = {m.recipient: m for m in msgs}
        new_hat = []
        for cid, (lo, hi) in enumerate(self._bounds(n, d)):
            r = _wire_reader(by_recipient[cid])
            sl = wcodecs.get_dense(r, hi - lo)
            r.expect_exhausted()
            new_hat.append(theta_hat[cid].at[lo:hi].set(jnp.asarray(sl)))
        return DownlinkResult(env.update.theta, jnp.stack(new_hat),
                              n * (d / n) * FLOAT_BITS)
