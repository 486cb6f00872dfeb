"""Wire-format suite: the bitstream layer must be lossless and auditable.

Four layers of contract, lowest first:

* **bitio** -- MSB-first packing round-trips arbitrary field widths and
  IEEE-754 f32 bit patterns exactly; misuse (overflow values, overruns,
  nonzero padding) fails loudly.
* **codecs** -- every channel-family payload (MRC indices, block-plan
  headers, sign bitmaps, top-k records, dense f32) round-trips bitwise
  and writes *exactly* the bits the BitMeter books for it.
* **framing** -- Message/WireSession serialize to one self-describing
  byte stream that parses back field-for-field; the golden file pins the
  byte-level layout (regenerate with ``REGEN_GOLDEN=1`` after a
  deliberate, DESIGN.md-documented format bump).
* **audit** -- for every registry scheme: the channel wire hooks decode
  to the exact arrays the direct path produces, and a 3-round
  ``wire="audit"`` engine run is bit-identical to the direct host run
  with the stream length reconciling against the booked bits.

The reconcile tolerance contract and the frame-header width are
tripwired against DESIGN.md: widening either constant without updating
the documented value is a test failure by construction.
"""
import math
import os
import pathlib
import re
from types import SimpleNamespace

from hypothesis import given, settings, strategies as st

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bernoulli import bern_kl, clip01
from repro.core.bitmeter import BitMeter, ReconcileError
from repro.core.quantizers import sign_bits, topk_bits
from repro.fl import registry
from repro.fl.channels import (BlockPlan, RoundContext, WireEnv,
                               decode_flush_up)
from repro.fl.data import make_synthetic, partition_iid
from repro.fl.engine import EngineSpec, FLEngine, MeanDeltaAggregator
from repro.fl.nets import make_mlp
from repro.fl.tasks import make_cfl_task, make_mask_task
from repro.wire import (DIR_CTRL, DIR_DOWN, DIR_FLUSH_DOWN, DIR_FLUSH_UP,
                        DIR_UP, DOWNLINK_DIRS, FRAME_HEADER_BITS,
                        FRAME_OVERHEAD_BITS, FRAME_TRAILER_BITS, MAGIC,
                        RECONCILE_REL_TOL, RECONCILE_TOL_BITS, SERVER,
                        UPLINK_DIRS, VERSION, BitReader, BitWriter, Message,
                        WireCapacityError, WireFormatError, WireSession,
                        codecs, scheme_wire_id)

N, D = 3, 96
SCHEMES = registry.all_schemes(n=N, d=D, n_is=8, block=32, reset_period=2,
                               include_adaptive=True)
SCHEME_IDS = [s[0] for s in SCHEMES]

REPO = pathlib.Path(__file__).resolve().parent.parent
GOLDEN = pathlib.Path(__file__).resolve().parent / "golden"

# Engine-level fixtures use a small MLP; keep ENGINE_D in sync (asserted
# in the fixture) so the registry's m3 top-k budget matches the model.
ENGINE_D = 208
ENGINE_SCHEMES = registry.all_schemes(n=N, d=ENGINE_D, n_is=8, block=32,
                                      reset_period=2, include_adaptive=True)


# ---------------------------------------------------------------------------
# bitio: MSB-first packing.
# ---------------------------------------------------------------------------


class TestBitIO:
    @settings(max_examples=8)
    @given(st.integers(min_value=0, max_value=2 ** 48 - 1),
           st.integers(min_value=1, max_value=48))
    def test_field_roundtrip(self, value, width):
        value &= (1 << width) - 1
        w = BitWriter()
        w.write(value, width)
        assert w.bits_written == width
        r = BitReader(w.getvalue(), w.bits_written)
        assert r.read(width) == value
        r.expect_exhausted()

    def test_mixed_width_stream_roundtrip(self):
        rng = np.random.default_rng(0)
        widths = rng.integers(1, 40, size=200)
        values = [int(rng.integers(0, 1 << wd)) for wd in widths]
        w = BitWriter()
        for v, wd in zip(values, widths):
            w.write(v, int(wd))
        assert w.bits_written == int(widths.sum())
        data = w.getvalue()
        assert len(data) == -(-w.bits_written // 8)
        assert w.getvalue() == data  # non-destructive
        r = BitReader(data, w.bits_written)
        for v, wd in zip(values, widths):
            assert r.read(int(wd)) == v
        r.expect_exhausted()

    def test_f32_bit_exact_roundtrip(self):
        specials = np.array([0.0, -0.0, 1.5, -2.25, np.inf, -np.inf,
                             np.nan, np.float32(1e-45),  # denormal
                             np.float32(3.4028235e38)], dtype=np.float32)
        for aligned in (True, False):
            w = BitWriter()
            if not aligned:
                w.write(1, 3)  # force the bit-by-bit path
            w.write_f32_array(specials)
            r = BitReader(w.getvalue(), w.bits_written)
            if not aligned:
                assert r.read(3) == 1
            out = r.read_f32_array(len(specials))
            np.testing.assert_array_equal(out.view(np.uint32),
                                          specials.view(np.uint32))

    def test_read_payload_unaligned_equals_aligned(self):
        rng = np.random.default_rng(1)
        payload = rng.integers(0, 256, size=17, dtype=np.uint8).tobytes()
        nbits = 131
        w = BitWriter()
        w.write_bits(payload, nbits)
        aligned, _ = BitReader(w.getvalue(), w.bits_written).read_payload(nbits)
        w2 = BitWriter()
        w2.write(0, 5)
        w2.write_bits(payload, nbits)
        r2 = BitReader(w2.getvalue(), w2.bits_written)
        r2.read(5)
        unaligned, _ = r2.read_payload(nbits)
        assert unaligned == aligned

    def test_misuse_is_loud(self):
        w = BitWriter()
        with pytest.raises(WireFormatError):
            w.write(4, 2)  # value does not fit
        with pytest.raises(WireFormatError):
            w.write(-1, 8)
        w.write(3, 2)
        r = BitReader(w.getvalue(), w.bits_written)
        with pytest.raises(WireFormatError):
            r.read(3)  # overruns the 2-bit stream
        with pytest.raises(WireFormatError):
            BitReader(b"\x00", 9)  # promises more bits than bytes
        ww = BitWriter()
        ww.write(3, 2)  # second bit is nonzero where padding is expected
        rr = BitReader(ww.getvalue(), 8)
        rr.read(1)
        with pytest.raises(WireFormatError):
            rr.align()

    def test_align_pads_with_zeros(self):
        w = BitWriter()
        w.write(5, 3)
        pad = w.align()
        assert pad == 5 and w.bits_written == 8
        r = BitReader(w.getvalue(), 8)
        assert r.read(3) == 5
        r.align()
        r.expect_exhausted()


# ---------------------------------------------------------------------------
# codecs: payloads write exactly the booked bits and round-trip bitwise.
# ---------------------------------------------------------------------------


class TestCodecs:
    def test_indices_roundtrip_at_booked_width(self):
        rng = np.random.default_rng(2)
        idx = rng.integers(0, 8, size=(2, 5, 3))
        w = BitWriter()
        codecs.put_indices(w, idx, 8)
        assert w.bits_written == idx.size * math.log2(8)  # booked rate
        r = BitReader(w.getvalue(), w.bits_written)
        np.testing.assert_array_equal(codecs.get_indices(r, idx.shape, 8), idx)
        r.expect_exhausted()

    def test_non_pow2_n_is_rejected(self):
        # log2(6) books fractional bits/index -- no integer codec can match
        with pytest.raises(WireCapacityError):
            codecs.index_width(6)

    def test_plan_avg_roundtrip(self):
        w = BitWriter()
        codecs.put_plan_avg(w, 64, 256)
        assert w.bits_written == math.ceil(math.log2(256))
        r = BitReader(w.getvalue(), w.bits_written)
        assert codecs.get_plan_avg(r, 256) == 64
        with pytest.raises(WireCapacityError):
            codecs.put_plan_avg(BitWriter(), 48, 256)  # not a pow2 size

    def test_plan_segments_roundtrip_self_delimiting(self):
        rng = np.random.default_rng(3)
        max_block = 64
        lengths = rng.integers(1, max_block + 1, size=9)
        seg = np.repeat(np.arange(len(lengths)), lengths)
        d = int(lengths.sum())
        w = BitWriter()
        codecs.put_plan_segments(w, seg, max_block)
        assert w.bits_written == len(lengths) * math.ceil(math.log2(max_block))
        r = BitReader(w.getvalue(), w.bits_written)
        np.testing.assert_array_equal(codecs.get_plan_segments(r, d,
                                                               max_block), seg)
        r.expect_exhausted()

    def test_plan_segments_capacity_and_tiling_errors(self):
        with pytest.raises(WireCapacityError):
            codecs.put_plan_segments(BitWriter(), np.zeros(65, np.int64), 64)
        w = BitWriter()
        w.write(7, 6)  # one segment of length 8 cannot tile d=5
        with pytest.raises(WireFormatError):
            codecs.get_plan_segments(BitReader(w.getvalue(), 6), 5, 64)

    def test_plan_segments_rejects_non_monotone(self):
        # the header is run-length coded: a permuted seg_ids has the same
        # bincount and would round-trip to a *different* segmentation
        good = np.repeat(np.arange(3), [2, 5, 1])
        with pytest.raises(WireFormatError, match="non-decreasing"):
            codecs.put_plan_segments(BitWriter(), good[::-1], 8)
        with pytest.raises(WireFormatError, match="non-decreasing"):
            codecs.put_plan_segments(BitWriter(), good + 1, 8)

    def test_sign_pass_roundtrip_at_booked_rate(self):
        rng = np.random.default_rng(4)
        d = 45  # not a byte multiple: bitmap padding is in the frame, not here
        signs = rng.random(d) < 0.5
        scale = np.float32(0.037)
        w = BitWriter()
        codecs.put_sign_pass(w, scale, signs)
        assert w.bits_written == sign_bits(d)  # d + 32
        r = BitReader(w.getvalue(), w.bits_written)
        s2, b2 = codecs.get_sign_pass(r, d)
        assert np.float32(s2).view(np.uint32) == scale.view(np.uint32)
        np.testing.assert_array_equal(b2, signs)
        r.expect_exhausted()

    def test_topk_roundtrip_at_booked_rate(self):
        rng = np.random.default_rng(5)
        d, k = 200, 7
        idx = rng.choice(d, size=k, replace=False)
        val = rng.standard_normal(k).astype(np.float32)
        w = BitWriter()
        codecs.put_topk(w, idx, val, d)
        assert w.bits_written == topk_bits(d, k)
        r = BitReader(w.getvalue(), w.bits_written)
        i2, v2 = codecs.get_topk(r, k, d)
        np.testing.assert_array_equal(i2, idx)
        np.testing.assert_array_equal(v2.view(np.uint32), val.view(np.uint32))
        r.expect_exhausted()

    def test_dense_roundtrip(self):
        rng = np.random.default_rng(6)
        xs = rng.standard_normal(33).astype(np.float32)
        w = BitWriter()
        codecs.put_dense(w, xs)
        assert w.bits_written == 32 * xs.size
        r = BitReader(w.getvalue(), w.bits_written)
        np.testing.assert_array_equal(codecs.get_dense(r, xs.size)
                                      .view(np.uint32), xs.view(np.uint32))
        r.expect_exhausted()


# ---------------------------------------------------------------------------
# Framing: messages and sessions.
# ---------------------------------------------------------------------------


class TestFraming:
    def test_header_width_is_pinned(self):
        assert FRAME_HEADER_BITS == 144
        assert FRAME_TRAILER_BITS == 32
        assert FRAME_OVERHEAD_BITS == 144 + 32 == 176
        assert MAGIC == 0xB1C0 and VERSION == 2

    def test_message_roundtrip(self):
        m = Message(direction=DIR_UP, sender=2, recipient=SERVER,
                    payload=b"\xAB\xC0", payload_bits=11, round=9,
                    scheme_id=0x1234)
        w = BitWriter()
        m.write_to(w)
        assert w.bits_written == m.frame_bits == FRAME_OVERHEAD_BITS + 16
        m2 = Message.read_from(BitReader(w.getvalue(), w.bits_written))
        assert m2 == m

    def test_message_validation(self):
        with pytest.raises(WireFormatError):
            Message(direction=99, sender=0, recipient=0, payload=b"",
                    payload_bits=0)
        with pytest.raises(WireFormatError):  # 1 byte cannot carry 9 bits
            Message(direction=DIR_UP, sender=0, recipient=0, payload=b"\x00",
                    payload_bits=9)
        with pytest.raises(WireFormatError):  # 2 bytes for 3 bits: over-padded
            Message(direction=DIR_UP, sender=0, recipient=0,
                    payload=b"\x00\x00", payload_bits=3)

    def test_session_roundtrip_and_direction_totals(self):
        s = WireSession(scheme_id=77)
        s.add([Message(direction=DIR_UP, sender=0, recipient=SERVER,
                       payload=b"\xF0", payload_bits=4),
               Message(direction=DIR_CTRL, sender=1, recipient=SERVER,
                       payload=b"\x80", payload_bits=1)], round=0)
        s.add([Message(direction=DIR_DOWN, sender=SERVER, recipient=0,
                       payload=b"\x01\x02\x03", payload_bits=24)], round=1)
        p = WireSession.parse(s.to_bytes())
        assert p.scheme_id == 77
        assert [(m.round, m.direction, m.sender, m.recipient, m.payload_bits,
                 m.payload) for m in p.messages] == \
               [(m.round, m.direction, m.sender, m.recipient, m.payload_bits,
                 m.payload) for m in s.messages]
        assert s.uplink_payload_bits == 5
        assert s.downlink_payload_bits == 24
        assert s.stream_bits == 3 * FRAME_OVERHEAD_BITS + 8 + 8 + 24
        lo = 3 * FRAME_OVERHEAD_BITS
        assert lo <= s.framing_bits <= lo + 3 * 7

    def test_parse_rejects_bad_magic_and_version(self):
        m = Message(direction=DIR_UP, sender=0, recipient=SERVER,
                    payload=b"", payload_bits=0)
        w = BitWriter()
        m.write_to(w)
        data = bytearray(w.getvalue())
        bad = bytes([0xDE, 0xAD]) + bytes(data[2:])
        with pytest.raises(WireFormatError, match="magic"):
            WireSession.parse(bad)
        data[2] = VERSION + 1
        with pytest.raises(WireFormatError, match="version"):
            WireSession.parse(bytes(data))


# ---------------------------------------------------------------------------
# Reconcile: loud on divergence, envelope-checked on framing.
# ---------------------------------------------------------------------------


class TestReconcile:
    def _meter(self, ul=1000.0, dl=500.0):
        m = BitMeter(n_clients=N, d=D)
        m.add_round(ul, dl)
        return m

    def test_exact_match_passes(self):
        rep = self._meter().reconcile(1000, 500, framing_bits=2 * 176,
                                      n_messages=2, frame_overhead_bits=176)
        assert rep["uplink_err_bits"] == 0.0
        assert rep["downlink_err_bits"] == 0.0

    def test_payload_divergence_raises(self):
        with pytest.raises(ReconcileError, match="uplink"):
            self._meter().reconcile(999, 500)
        with pytest.raises(ReconcileError, match="downlink"):
            self._meter().reconcile(1000, 501)

    def test_rel_tol_absorbs_float_bookkeeping_only(self):
        m = self._meter(ul=1e9)
        m.reconcile(1e9 + 0.5, 500)  # within 1e-9 relative slack
        with pytest.raises(ReconcileError):
            m.reconcile(1e9 + 10.0, 500)

    def test_framing_envelope_raises(self):
        with pytest.raises(ReconcileError, match="framing"):
            self._meter().reconcile(1000, 500, framing_bits=10.0,
                                    n_messages=2, frame_overhead_bits=176)
        with pytest.raises(ReconcileError, match="framing"):
            self._meter().reconcile(1000, 500,
                                    framing_bits=2 * (176 + 7) + 1,
                                    n_messages=2, frame_overhead_bits=176)

    def test_session_reconcile_is_loud(self):
        s = WireSession(scheme_id=1)
        s.add([Message(direction=DIR_UP, sender=0, recipient=SERVER,
                       payload=b"\x00" * 125, payload_bits=1000)], round=0)
        m = BitMeter(n_clients=N, d=D)
        m.add_round(1000.0, 0.0)
        s.reconcile(m)  # exact: passes
        m.add_round(1.0, 0.0)  # book a bit that never hit the wire
        with pytest.raises(ReconcileError):
            s.reconcile(m)


# ---------------------------------------------------------------------------
# Channel-level audit: hooks are lossless and write the booked bits.
# (Same fixture pattern as tests/test_bit_accounting.py.)
# ---------------------------------------------------------------------------


def _round_inputs(kind: str, key: int = 0):
    rng = np.random.default_rng(key)
    if kind == "mask":
        payload = jnp.asarray(rng.uniform(0.05, 0.95, (N, D)), jnp.float32)
        priors = jnp.asarray(rng.uniform(0.05, 0.95, (N, D)), jnp.float32)
        theta = jnp.asarray(rng.uniform(0.05, 0.95, D), jnp.float32)
    else:
        payload = jnp.asarray(rng.standard_normal((N, D)), jnp.float32)
        priors = jnp.asarray(rng.standard_normal((N, D)), jnp.float32)
        theta = jnp.asarray(rng.standard_normal(D), jnp.float32)
    return payload, priors, theta


def _host_plan(spec, payload, priors):
    if spec.allocation is None:
        return None
    kl = None
    if getattr(spec.allocation, "needs_kl", True):
        kl = np.asarray(jnp.mean(jax.vmap(bern_kl)(payload, clip01(priors)),
                                 axis=0))
    size, n_blocks, seg_ids, overhead = spec.allocation.plan(kl, D)
    return BlockPlan(size=size, n_blocks=n_blocks, seg_ids=seg_ids,
                     overhead_bits=overhead)


def _ctx(spec, payload, priors):
    plan = _host_plan(spec, payload, priors)
    return RoundContext(t=0, key=jax.random.PRNGKey(7), n_clients=N, d=D,
                        active=np.arange(N), plan=plan)


def _bits_close(stream_bits, booked):
    return math.isclose(stream_bits, booked,
                        rel_tol=RECONCILE_REL_TOL, abs_tol=RECONCILE_TOL_BITS)


@pytest.mark.parametrize("name,kind,factory", SCHEMES, ids=SCHEME_IDS)
def test_channel_hooks_lossless_and_stream_matches_booked(name, kind, factory):
    spec = factory()
    payload, priors, theta = _round_inputs(kind)
    ctx = _ctx(spec, payload, priors)
    theta_hat = jnp.tile(theta[None], (N, 1))

    up_s = spec.uplink.init_up_state(N, D)
    dn_s = spec.downlink.init_down_state(N, D)

    # direct reference round
    up_direct, ul_direct, up_s1 = spec.uplink.step_up(ctx, up_s, payload,
                                                      priors)
    update = spec.aggregator(ctx, theta, up_direct)
    res_d, dn_s1 = spec.downlink.step_down(ctx, dn_s, update, theta,
                                           theta_hat)
    th_d, thh_d, dl_direct = res_d

    # wire round: encode -> decode drives everything
    _, ul_wire, up_s2, up_msgs = spec.uplink.encode_up(ctx, up_s, payload,
                                                       priors)
    up_dec = spec.uplink.decode_up(ctx, up_msgs, priors)
    np.testing.assert_array_equal(np.asarray(up_dec), np.asarray(up_direct))
    assert ul_wire == ul_direct, name
    for a, b in zip(jax.tree.leaves(up_s2), jax.tree.leaves(up_s1)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    update_w = spec.aggregator(ctx, theta, up_dec)
    _, dn_s2, dn_msgs = spec.downlink.encode_down(ctx, dn_s, update_w, theta,
                                                  theta_hat, up_msgs)
    for a, b in zip(jax.tree.leaves(dn_s2), jax.tree.leaves(dn_s1)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    env = WireEnv(uplink=spec.uplink, aggregator=spec.aggregator,
                  priors=priors, up_msgs=up_msgs, update=update_w)
    th_w, thh_w, dl_wire = spec.downlink.decode_down(ctx, dn_msgs, theta,
                                                     theta_hat, env)
    np.testing.assert_array_equal(np.asarray(th_w), np.asarray(th_d))
    np.testing.assert_array_equal(np.asarray(thh_w), np.asarray(thh_d))
    assert dl_wire == dl_direct, name

    # serialized payload length == booked channel bits, per direction
    assert all(m.direction == DIR_UP for m in up_msgs), name
    assert all(m.direction == DIR_DOWN for m in dn_msgs), name
    assert _bits_close(sum(m.payload_bits for m in up_msgs), ul_direct), name
    assert _bits_close(sum(m.payload_bits for m in dn_msgs), dl_direct), name


@pytest.mark.parametrize("name,kind,factory",
                         [s for s in SCHEMES if s[2]().allocation is not None],
                         ids=[s[0] for s in SCHEMES
                              if s[2]().allocation is not None])
def test_plan_header_roundtrip_at_booked_overhead(name, kind, factory):
    spec = factory()
    payload, priors, _ = _round_inputs(kind)
    plan = _host_plan(spec, payload, priors)
    w = BitWriter()
    spec.allocation.encode_plan(plan, w)
    assert w.bits_written == plan.overhead_bits, name  # header == booked
    r = BitReader(w.getvalue(), w.bits_written)
    plan2 = spec.allocation.decode_plan(r, D)
    r.expect_exhausted()
    assert plan2.size == plan.size and plan2.n_blocks == plan.n_blocks, name
    assert float(plan2.overhead_bits) == float(plan.overhead_bits), name
    if plan.seg_ids is None:
        assert plan2.seg_ids is None, name
    else:
        np.testing.assert_array_equal(np.asarray(plan2.seg_ids),
                                      np.asarray(plan.seg_ids))


@pytest.mark.parametrize("scheme", ["cser", "liec"])
def test_flush_wire_matches_flush(scheme):
    spec = registry.baseline_spec(scheme, n=N, d=D, reset_period=2)
    payload, priors, theta = _round_inputs("delta")
    ctx = _ctx(spec, payload, priors)
    # one round populates the EF memories
    _, _, e = spec.uplink.step_up(ctx, spec.uplink.init_up_state(N, D),
                                  payload, priors)
    r1, b1, e1 = spec.uplink.flush_step(e, N, D)
    _, b2, e2, msgs = spec.uplink.encode_flush_up(e, N, D)
    assert b2 == b1
    np.testing.assert_array_equal(np.asarray(e2), np.asarray(e1))
    assert len(msgs) == N
    assert all(m.direction == DIR_FLUSH_UP for m in msgs)
    assert _bits_close(sum(m.payload_bits for m in msgs), b1)
    dec = decode_flush_up(msgs, N, D)
    np.testing.assert_array_equal(np.asarray(dec), np.asarray(r1))


# ---------------------------------------------------------------------------
# Engine-level audit: a wire-audited run is bit-identical to the direct run.
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def wire_setup():
    k = jax.random.PRNGKey(6)
    train, test = make_synthetic(k, n_train=60, n_test=30, hw=4, noise=0.5)
    shards = partition_iid(jax.random.fold_in(k, 1), train, N, 20)
    net = make_mlp(in_dim=16, widths=(8,), signed_constant=True)
    mask_task = make_mask_task(net, jax.random.fold_in(k, 2), test.x, test.y,
                               local_epochs=1, batch_size=20)
    dnet = make_mlp(in_dim=16, widths=(8,))
    cfl_task, theta0 = make_cfl_task(dnet, jax.random.fold_in(k, 3), test.x,
                                     test.y, local_epochs=1, batch_size=20,
                                     local_lr=3e-3)
    assert int(theta0.shape[0]) == ENGINE_D  # keep ENGINE_SCHEMES' d in sync
    return mask_task, cfl_task, theta0, shards


@pytest.mark.parametrize("name,kind,factory", ENGINE_SCHEMES,
                         ids=[s[0] for s in ENGINE_SCHEMES])
def test_wire_audited_run_bit_identical(name, kind, factory, wire_setup):
    mask_task, cfl_task, theta0, shards = wire_setup
    task = mask_task if kind == "mask" else cfl_task
    t0 = None if kind == "mask" else theta0
    # reset_period=2 inside 3 rounds exercises the FLUSH_UP/FLUSH_DOWN frames
    direct = FLEngine(task, factory()).run(shards, t0, rounds=3, seed=1,
                                           mode="host")
    audited = FLEngine(task, factory()).run(shards, t0, rounds=3, seed=1,
                                            mode="host", wire="audit")

    np.testing.assert_array_equal(np.asarray(direct["theta"]),
                                  np.asarray(audited["theta"]))
    np.testing.assert_array_equal(np.asarray(direct["theta_hat"]),
                                  np.asarray(audited["theta_hat"]))
    assert audited["history"] == direct["history"], name
    assert audited["meter"] == direct["meter"], name

    # the reconcile report certifies stream length == booked bits
    rep = audited["wire"]
    assert rep["messages"] > 0, name
    session = audited["wire_session"]
    assert all(m.scheme_id == scheme_wire_id(factory().name)
               for m in session.messages), name

    # the stream survives serialization field-for-field
    parsed = WireSession.parse(session.to_bytes())
    assert [(m.round, m.direction, m.sender, m.recipient, m.payload_bits,
             m.payload) for m in parsed.messages] == \
           [(m.round, m.direction, m.sender, m.recipient, m.payload_bits,
             m.payload) for m in session.messages], name


def test_wire_audit_rejects_fused_mode(wire_setup):
    mask_task, _, _, shards = wire_setup
    eng = FLEngine(mask_task, ENGINE_SCHEMES[0][2]())
    with pytest.raises(ValueError, match="host path"):
        eng.run(shards, rounds=1, mode="fused", wire="audit")
    with pytest.raises(ValueError, match="wire="):
        eng.run(shards, rounds=1, mode="host", wire="bogus")


def test_wire_audit_rejects_unwireable_spec(wire_setup):
    mask_task, _, _, shards = wire_setup
    spec = EngineSpec(uplink=SimpleNamespace(), downlink=SimpleNamespace(),
                      aggregator=MeanDeltaAggregator(), name="no-wire")
    with pytest.raises(ValueError, match="cannot be wire-audited"):
        FLEngine(mask_task, spec).run(shards, rounds=1, mode="host",
                                      wire="audit")


def test_wire_audit_rejects_non_pow2_n_is_upfront(wire_setup):
    """A fractional-bit n_is must fail before any round work, naming the
    offending channel -- not as a WireCapacityError mid-run."""
    from repro.core.blocks import FixedAllocation
    mask_task, _, _, shards = wire_setup
    spec = registry.bicompfl_spec("GR", allocation=FixedAllocation(32),
                                  n_is=6, n_dl=N)
    eng = FLEngine(mask_task, spec)
    with pytest.raises(ValueError,
                       match=r"MRCFixedChannel has n_is=6"):
        eng.run(shards, rounds=3, seed=1, mode="host", wire="audit")
    # off the wire, a non-pow2 n_is is perfectly legal (bits are booked
    # at the information-theoretic log2 rate)
    out = FLEngine(mask_task, spec).run(shards, rounds=1, seed=1, mode="host")
    assert len(out["history"]) == 1


def test_registry_schemes_have_wireable_n_is():
    """Every registry scheme's channels book integer bits per MRC index."""
    for name, _, factory in SCHEMES:
        spec = factory()
        for chan in (spec.uplink, spec.downlink):
            n_is = getattr(chan, "n_is", None)
            if n_is is not None:
                codecs.index_width(n_is)  # raises WireCapacityError if not


def test_scheme_wire_ids_fit_header_without_collision():
    ids = registry.wire_scheme_ids(n=N, d=D)
    # adaptive variants reuse their base spec name -> distinct names, not rows
    names = {f().name for _, _, f in SCHEMES}
    assert set(ids) == names
    assert all(0 <= v <= 0xFFFF for v in ids.values())
    assert len(set(ids.values())) == len(ids)  # one header id per scheme


# ---------------------------------------------------------------------------
# Fused-program cache (PR satellite): repeated runs must not retrace.
# ---------------------------------------------------------------------------


def test_fused_program_cache_no_retrace(wire_setup):
    _, cfl_task, theta0, shards = wire_setup
    spec = lambda: registry.baseline_spec("fedavg", n=N, d=ENGINE_D)
    eng = FLEngine(cfl_task, spec())
    cold = eng.run(shards, theta0, rounds=2, seed=0, mode="fused")
    assert eng.fused_trace_count == 1
    # seed and eval cadence are runner *data*: cache hits, no retrace
    warm = eng.run(shards, theta0, rounds=2, seed=5, mode="fused")
    assert eng.fused_trace_count == 1
    eng.run(shards, theta0, rounds=2, seed=5, eval_every=2, mode="fused")
    assert eng.fused_trace_count == 1
    # a shape change (rounds) is a new signature: exactly one more trace
    eng.run(shards, theta0, rounds=3, seed=0, mode="fused")
    assert eng.fused_trace_count == 2

    # warm-path results are identical to a cold engine's
    fresh = FLEngine(cfl_task, spec()).run(shards, theta0, rounds=2, seed=5,
                                           mode="fused")
    np.testing.assert_array_equal(np.asarray(warm["theta"]),
                                  np.asarray(fresh["theta"]))
    assert warm["meter"] == fresh["meter"]
    assert warm["history"] == fresh["history"]
    assert cold["meter"]["rounds"] == 2


# ---------------------------------------------------------------------------
# Golden file: byte-level format stability.
# ---------------------------------------------------------------------------


def _golden_session() -> WireSession:
    """A deterministic session exercising every codec family."""
    s = WireSession(scheme_id=scheme_wire_id("golden-v1"))

    w = BitWriter()
    codecs.put_plan_segments(w, np.repeat(np.arange(3), [2, 5, 1]), 8)
    ctrl = Message(direction=DIR_CTRL, sender=0, recipient=SERVER,
                   payload=w.getvalue(), payload_bits=w.bits_written)

    w = BitWriter()
    codecs.put_indices(w, np.arange(12).reshape(3, 4) % 8, 8)
    up_idx = Message(direction=DIR_UP, sender=1, recipient=SERVER,
                     payload=w.getvalue(), payload_bits=w.bits_written)

    w = BitWriter()
    codecs.put_sign_pass(w, np.float32(0.5), [True, False] * 8 + [True])
    up_sign = Message(direction=DIR_UP, sender=2, recipient=SERVER,
                      payload=w.getvalue(), payload_bits=w.bits_written)

    w = BitWriter()
    codecs.put_topk(w, [3, 11, 4], np.float32([1.5, -2.25, 0.125]), 16)
    up_topk = Message(direction=DIR_FLUSH_UP, sender=0, recipient=SERVER,
                      payload=w.getvalue(), payload_bits=w.bits_written)

    w = BitWriter()
    codecs.put_dense(w, np.float32([0.0, -0.0, 3.5, -1e-8]))
    down = Message(direction=DIR_DOWN, sender=SERVER, recipient=1,
                   payload=w.getvalue(), payload_bits=w.bits_written)
    w = BitWriter()
    codecs.put_dense(w, np.float32([2.0, -4.0]))
    flush_dn = Message(direction=DIR_FLUSH_DOWN, sender=SERVER, recipient=2,
                       payload=w.getvalue(), payload_bits=w.bits_written)

    s.add([ctrl, up_idx, up_sign], round=0)
    s.add([up_topk, down, flush_dn], round=1)
    return s


def test_golden_wire_file_is_stable():
    """The serialized byte stream is the format contract.  A mismatch means
    the wire layout changed: bump VERSION, document the change in
    DESIGN.md, and regenerate with ``REGEN_GOLDEN=1 pytest -k golden``."""
    path = GOLDEN / "wire_session_v2.bin"
    data = _golden_session().to_bytes()
    if os.environ.get("REGEN_GOLDEN"):
        GOLDEN.mkdir(exist_ok=True)
        path.write_bytes(data)
    assert path.exists(), f"golden file missing; regenerate: " \
                          f"REGEN_GOLDEN=1 pytest {__file__} -k golden"
    assert path.read_bytes() == data

    # and it parses back to the exact field values written above
    p = WireSession.parse(path.read_bytes())
    assert p.scheme_id == scheme_wire_id("golden-v1")
    assert [m.direction for m in p.messages] == \
           [DIR_CTRL, DIR_UP, DIR_UP, DIR_FLUSH_UP, DIR_DOWN, DIR_FLUSH_DOWN]
    assert [m.round for m in p.messages] == [0, 0, 0, 1, 1, 1]
    r = BitReader(p.messages[0].payload, p.messages[0].payload_bits)
    np.testing.assert_array_equal(codecs.get_plan_segments(r, 8, 8),
                                  np.repeat(np.arange(3), [2, 5, 1]))
    r = BitReader(p.messages[1].payload, p.messages[1].payload_bits)
    np.testing.assert_array_equal(
        codecs.get_indices(r, (3, 4), 8), np.arange(12).reshape(3, 4) % 8)
    r = BitReader(p.messages[4].payload, p.messages[4].payload_bits)
    np.testing.assert_array_equal(
        codecs.get_dense(r, 4).view(np.uint32),
        np.float32([0.0, -0.0, 3.5, -1e-8]).view(np.uint32))


# ---------------------------------------------------------------------------
# DESIGN.md tripwire: the documented contract must equal the code constants.
# ---------------------------------------------------------------------------


def test_design_doc_pins_the_tolerance_contract():
    """Widening a reconcile tolerance or the frame header without updating
    the documented contract in DESIGN.md is a format change done wrong."""
    text = (REPO / "DESIGN.md").read_text()

    def documented(name):
        m = re.search(rf"`{name}`\s*=\s*([0-9e.+-]+)", text)
        assert m, f"DESIGN.md does not document {name}"
        return float(m.group(1))

    assert documented("FRAME_HEADER_BITS") == FRAME_HEADER_BITS == 144
    assert documented("RECONCILE_TOL_BITS") == RECONCILE_TOL_BITS == 0.0
    assert documented("RECONCILE_REL_TOL") == RECONCILE_REL_TOL == 1e-9
    assert documented("WIRE_VERSION") == VERSION == 2
    assert documented("FRAME_TRAILER_BITS") == FRAME_TRAILER_BITS == 32
    assert documented("FRAME_OVERHEAD_BITS") == FRAME_OVERHEAD_BITS == 176
