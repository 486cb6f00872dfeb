"""Minimal Random Coding (MRC) with shared randomness -- the paper's C_mrc.

Two parties hold a common *prior* P (Bernoulli parameter vector) and shared
randomness (a counter-based PRNG key).  The encoder additionally holds a
*posterior* Q and wants the decoder to obtain a sample ~Q.  Both sides derive
the same ``n_is`` candidates X_1..X_{n_is} ~ P; the encoder forms the
importance distribution

    W(i) proportional to Q(X_i) / P(X_i)

samples an index I ~ W (Gumbel-max) and transmits only I  --  log2(n_is) bits.

The model vector of dimension d is partitioned into B blocks; MRC runs
independently per block (the paper's "B blocks of size d/B"), so the uplink
cost is B * log2(n_is) bits per conveyed sample.

Two codec paths are provided:

* **fixed blocks** (`encode_fixed` / `decode_fixed`): all blocks have the same
  static size.  A block's candidates are one threefry stream,
  ``uniform(fold_in(key, block), (n_is, S))``, and the transmitted sample is
  regenerated from the *selected row's own counters* (``_candidate_row``) on
  both sides -- decode is O(d), not O(d * n_is).  The importance-weight
  evaluation is the matvec ``logW = X @ a + sum(b)`` (see
  ``core.bernoulli.log_ratio_coeffs``) and can be routed through the Pallas
  TPU kernel in ``repro.kernels``.

* **segments** (`encode_segments` / `decode_segments`): variable-size blocks
  described by a segment-id vector, used by the Adaptive allocation of Isik
  et al. (2024).  The weight evaluation is pluggable via ``seg_logw_fn``:
  the jnp default materialises the (n_is, d) candidate tensor; the Pallas
  segment-logW kernel (``repro.kernels.ops.segment_logw_fn``) streams it
  through VMEM instead.  ``seg_ids`` must be non-decreasing starting at 0
  (the wire plan header is run-length coded); the codec boundary validates
  this whenever the vector is concrete.
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend.random import threefry2x32_p

from .bernoulli import clip01, log_ratio_coeffs

# ---------------------------------------------------------------------------
# Key derivation (the "shared randomness" of the paper, threefry counters).
# ---------------------------------------------------------------------------


def round_key(base: jax.Array, t) -> jax.Array:
    """Shared key for global round t."""
    return jax.random.fold_in(base, t)


def client_key(base: jax.Array, client_id) -> jax.Array:
    """Private shared randomness between the federator and one client."""
    return jax.random.fold_in(jax.random.fold_in(base, 0x5EED), client_id)


def sample_key(base: jax.Array, ell) -> jax.Array:
    """Per conveyed-sample (ell in [n_UL] or [n_DL]) candidate key."""
    return jax.random.fold_in(base, ell)


def _block_candidates(shared_key: jax.Array, block_id, n_is: int, size: int) -> jax.Array:
    """All n_is candidate uniform rows for one block: (n_is, size).

    One threefry stream per block (cheap); both sides derive the identical
    tensor, which is all the shared-randomness assumption requires.
    """
    return jax.random.uniform(jax.random.fold_in(shared_key, block_id), (n_is, size))


def _candidate_row(block_key: jax.Array, row, n_is: int, size: int) -> jax.Array:
    """Row ``row`` of ``_block_candidates``' ``(n_is, size)`` tensor: (size,).

    Only the row's ``size`` uniforms are computed, each from its own threefry
    counter, bit for bit as ``jax.random.uniform(block_key, (n_is, size))``
    draws them: with ``jax_threefry_partitionable`` on, element ``(i, s)``
    takes the counter pair ``(0, i*size + s)``, its 32 bits are the XOR of
    the two output words, and the top 23 become the mantissa of a float in
    [1, 2) less 1.  Takes raw ``uint32[2]`` and typed threefry keys.
    """
    if not jax.config.jax_threefry_partitionable:
        raise ValueError(
            "_candidate_row reproduces the partitionable threefry stream; "
            "jax_threefry_partitionable=False lays out another")
    if jax.dtypes.issubdtype(block_key.dtype, jax.dtypes.prng_key):
        impl = str(jax.random.key_impl(block_key))
        data = jax.random.key_data(block_key)
    else:
        impl, data = jax.config.jax_default_prng_impl, block_key
    if impl != "threefry2x32":
        raise ValueError(f"_candidate_row needs threefry2x32 keys, got {impl}")
    if n_is * size > 2 ** 32:
        raise ValueError(
            f"n_is * size = {n_is * size} candidates exceed the 2**32 counters "
            "of one threefry word")
    lo = (jnp.asarray(row, jnp.uint32) * jnp.uint32(size)
          + jax.lax.iota(jnp.uint32, size))
    # ``hi`` (all zero) is derived from ``lo`` so that both counter words
    # carry the same batch dimensions under vmap.
    bits1, bits2 = threefry2x32_p.bind(data[0], data[1], lo & jnp.uint32(0), lo)
    mantissa = jax.lax.shift_right_logical(bits1 ^ bits2, jnp.uint32(9))
    one_to_two = jax.lax.bitcast_convert_type(
        mantissa | jnp.uint32(0x3F800000), jnp.float32)
    return one_to_two - jnp.float32(1.0)


def _selected_samples(shared_key: jax.Array, block_ids: jax.Array,
                      rows: jax.Array, p_blocks: jax.Array, n_is: int) -> jax.Array:
    """The {0,1} samples of the selected candidate rows: (nb, S).

    Encoder and decoder both build the transmitted sample here, each block
    from its one selected row and never from the block's ``n_is`` rows.
    """
    size = p_blocks.shape[-1]

    def one(bid, row, pb):
        u = _candidate_row(jax.random.fold_in(shared_key, bid), row, n_is, size)
        return (u < clip01(pb)).astype(jnp.float32)

    with jax.named_scope("mrc.draw"):
        return jax.vmap(one)(block_ids, rows, p_blocks)


# ---------------------------------------------------------------------------
# Fixed-size block codec.
# ---------------------------------------------------------------------------

LogWFn = Callable[[jax.Array, jax.Array, jax.Array], jax.Array]
# signature: (X: (nb, n_is, S) {0,1}, a: (nb, S), b: (nb, S)) -> (nb, n_is)


def default_logw(x: jax.Array, a: jax.Array, b: jax.Array) -> jax.Array:
    """Pure-jnp importance log-weights: logW = X @ a + sum(b)."""
    return jnp.einsum("bis,bs->bi", x, a) + jnp.sum(b, axis=-1, keepdims=True)


class MRCResult(NamedTuple):
    indices: jax.Array  # (B,) int32 -- what actually goes over the wire
    sample: jax.Array   # (B, S) {0,1} -- decoder-side reconstruction


@functools.partial(jax.jit, static_argnames=("n_is", "chunk", "logw_fn"))
def encode_fixed(
    shared_key: jax.Array,
    select_key: jax.Array,
    q: jax.Array,
    p: jax.Array,
    *,
    n_is: int,
    chunk: int = 32,
    logw_fn: Optional[LogWFn] = None,
) -> MRCResult:
    """MRC-encode posterior q against prior p, both (B, S) block matrices.

    Returns the transmitted indices and the sample the decoder will see
    (identical to what `decode_fixed` reconstructs from the indices).
    """
    logw_impl = logw_fn if logw_fn is not None else default_logw
    B, S = q.shape
    nb = min(chunk, B)
    n_chunks = -(-B // nb)
    pad = n_chunks * nb - B
    if pad:
        # Padding blocks carry q == p == 0.5: zero KL, index discarded later.
        halfq = jnp.full((pad, S), 0.5, q.dtype)
        q = jnp.concatenate([q, halfq])
        p = jnp.concatenate([p, halfq])

    with jax.named_scope("mrc.logw"):
        a, b = log_ratio_coeffs(q, p)  # (B', S) each

    def chunk_body(c):
        block_ids = c * nb + jnp.arange(nb)
        pc = jax.lax.dynamic_slice_in_dim(p, c * nb, nb, axis=0)  # (nb, S)
        ac = jax.lax.dynamic_slice_in_dim(a, c * nb, nb, axis=0)
        bc = jax.lax.dynamic_slice_in_dim(b, c * nb, nb, axis=0)
        with jax.named_scope("mrc.logw"):
            # One pass over the candidates: XLA fuses their threefry, the
            # compare and the default weights' reduction; a ``logw_fn``
            # kernel takes the 0/1 tensor as its input.
            u = jax.vmap(lambda bid: _block_candidates(shared_key, bid, n_is, S))(block_ids)
            x = (u < clip01(pc)[:, None, :]).astype(jnp.float32)
            logw = logw_impl(x, ac, bc)  # (nb, n_is)
        gu = jax.vmap(
            lambda bid: jax.random.uniform(jax.random.fold_in(select_key, bid), (n_is,))
        )(block_ids)
        gumbel = -jnp.log(-jnp.log(jnp.clip(gu, 1e-12, 1.0 - 1e-12)))
        idx = jnp.argmax(logw + gumbel, axis=-1).astype(jnp.int32)  # (nb,)
        chosen = _selected_samples(shared_key, block_ids, idx, pc, n_is)  # (nb, S)
        return idx, chosen

    idxs, chosen = jax.lax.map(chunk_body, jnp.arange(n_chunks))
    idxs = idxs.reshape(-1)[:B]
    chosen = chosen.reshape(-1, S)[:B]
    return MRCResult(indices=idxs, sample=chosen)


@functools.partial(jax.jit, static_argnames=("n_is",))
def decode_fixed(shared_key: jax.Array, indices: jax.Array, p: jax.Array, *, n_is: int) -> jax.Array:
    """Reconstruct the encoder-selected sample from the indices: (B, S)."""
    return _selected_samples(shared_key, jnp.arange(p.shape[0]), indices, p, n_is)


def transmit_fixed(
    shared_key: jax.Array,
    select_key: jax.Array,
    q: jax.Array,
    p: jax.Array,
    *,
    n_is: int,
    n_samples: int = 1,
    chunk: int = 32,
    logw_fn: Optional[LogWFn] = None,
):
    """Convey ``n_samples`` i.i.d. MRC samples of q (fresh candidates per ell).

    Returns (indices (n_samples, B), mean_sample (B, S)). ``mean_sample`` is
    the decoder-side estimate  q_hat = 1/n_samples * sum_ell x_ell .
    """
    def one(ell):
        res = encode_fixed(
            sample_key(shared_key, ell),
            sample_key(select_key, ell),
            q,
            p,
            n_is=n_is,
            chunk=chunk,
            logw_fn=logw_fn,
        )
        return res.indices, res.sample

    idxs, samples = jax.lax.map(one, jnp.arange(n_samples))
    return idxs, jnp.mean(samples, axis=0)


def receive_fixed(shared_key: jax.Array, indices: jax.Array, p: jax.Array, *, n_is: int) -> jax.Array:
    """Decode n_samples relayed index vectors: indices (n_samples, B) -> (B, S)."""
    samples = jax.vmap(
        lambda ell, idx: decode_fixed(sample_key(shared_key, ell), idx, p, n_is=n_is)
    )(jnp.arange(indices.shape[0]), indices)
    return jnp.mean(samples, axis=0)


# ---------------------------------------------------------------------------
# Variable-size (segment) codec for Adaptive block allocation.
# ---------------------------------------------------------------------------


def _segment_candidates(shared_key: jax.Array, n_is: int, d: int) -> jax.Array:
    rows = jnp.arange(n_is)
    return jax.vmap(lambda r: jax.random.uniform(jax.random.fold_in(shared_key, r), (d,)))(rows)


SegLogWFn = Callable[[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array, int], jax.Array]
# signature: (u: (n_is, d) uniforms, p: (d,) clipped prior, a: (d,),
#             b: (d,), seg_ids: (d,), n_seg) -> (n_is, n_seg)


def default_segment_logw(u: jax.Array, p: jax.Array, a: jax.Array,
                         b: jax.Array, seg_ids: jax.Array, n_seg: int) -> jax.Array:
    """Pure-jnp segment log-weights: vmapped segment_sum over the fused
    compare+select ``where(u < p, a, 0)`` (materialises (n_is, d) in HBM;
    the Pallas route in ``repro.kernels.ops.segment_logw`` does not)."""
    xa = jnp.where(u < p[None, :], a[None, :], 0.0)             # (n_is, d)
    seg_sum = lambda row: jax.ops.segment_sum(row, seg_ids, num_segments=n_seg)
    return jax.vmap(seg_sum)(xa) + seg_sum(b)[None, :]          # (n_is, n_seg)


def _validate_seg_ids(seg_ids) -> None:
    """Host-side check of the segment-codec contract.

    The wire block-plan header (``wire.codecs.put_plan_segments``) encodes a
    segmentation as run-lengths, so a permuted ``seg_ids`` would round-trip
    the header to a *different* segmentation and decode a wrong sample with
    no error.  Enforce non-decreasing ids starting at 0 whenever the vector
    is concrete; traced ``seg_ids`` (the fused engine's bucketed plans, which
    are cumsum-built and monotone by construction) skip the check.
    """
    if isinstance(seg_ids, jax.core.Tracer):
        return
    seg = np.asarray(seg_ids)
    if seg.ndim != 1 or seg.size == 0:
        raise ValueError(
            f"seg_ids must be a non-empty 1-D vector, got shape {seg.shape}")
    if int(seg[0]) != 0 or np.any(np.diff(seg) < 0):
        raise ValueError(
            "seg_ids must be non-decreasing and start at 0: the wire plan "
            "header stores segments as run-lengths, so any other ordering "
            "round-trips to a different segmentation")


@functools.partial(jax.jit, static_argnames=("n_is", "n_seg", "seg_logw_fn"))
def _encode_segments(
    shared_key: jax.Array,
    select_key: jax.Array,
    q: jax.Array,
    p: jax.Array,
    seg_ids: jax.Array,
    *,
    n_is: int,
    n_seg: int,
    seg_logw_fn: Optional[SegLogWFn] = None,
) -> MRCResult:
    logw_impl = seg_logw_fn if seg_logw_fn is not None else default_segment_logw
    pc = clip01(p)
    with jax.named_scope("mrc.draw"):
        u = _segment_candidates(shared_key, n_is, q.shape[0])   # (n_is, d)
    with jax.named_scope("mrc.logw"):
        a, b = log_ratio_coeffs(q, p)                           # (d,), (d,)
        logw = logw_impl(u, pc, a, b, seg_ids, n_seg)           # (n_is, n_seg)
    gu = jax.random.uniform(select_key, (n_is, n_seg))
    gumbel = -jnp.log(-jnp.log(jnp.clip(gu, 1e-12, 1.0 - 1e-12)))
    idx = jnp.argmax(logw + gumbel, axis=0).astype(jnp.int32)   # (n_seg,)
    u_sel = jnp.take_along_axis(u, idx[seg_ids][None, :], axis=0)[0]  # (d,)
    chosen = (u_sel < pc).astype(jnp.float32)
    return MRCResult(indices=idx, sample=chosen)


def encode_segments(
    shared_key: jax.Array,
    select_key: jax.Array,
    q: jax.Array,
    p: jax.Array,
    seg_ids: jax.Array,
    *,
    n_is: int,
    n_seg: int,
    seg_logw_fn: Optional[SegLogWFn] = None,
) -> MRCResult:
    """MRC over variable blocks given per-parameter segment ids (d,).

    The importance weights decompose as  logW(i, s) = sum_{e in s} x_ie*a_e
    + sum_{e in s} b_e : the prior term is candidate-independent, so it is
    segment-summed once ((d,) -> (n_seg,)) instead of being broadcast into
    an (n_is, d) add, and the candidate term streams through one fused
    compare+select pass over the uniforms (``where(u < p, a, 0)`` -- exact:
    x is {0, 1} and a is finite after clipping).  The selected sample is
    re-thresholded from the chosen candidate *row* only, never from a
    materialised (n_is, d) sample tensor.  This is the fused adaptive
    path's per-round hot loop (every client, every sample).

    ``seg_logw_fn`` makes the weight evaluation pluggable the way
    ``logw_fn`` is for ``encode_fixed``: pass
    ``repro.kernels.ops.segment_logw_fn()`` to route it through the Pallas
    segment-logW kernel (streams u once, never materialises (n_is, d)).
    It is a static jit argument hashed by identity -- hand in a cached
    closure, not a fresh lambda per call.
    """
    _validate_seg_ids(seg_ids)
    return _encode_segments(shared_key, select_key, q, p, seg_ids,
                            n_is=n_is, n_seg=n_seg, seg_logw_fn=seg_logw_fn)


@functools.partial(jax.jit, static_argnames=("n_is",))
def _decode_segments(
    shared_key: jax.Array, indices: jax.Array, p: jax.Array, seg_ids: jax.Array, *, n_is: int
) -> jax.Array:
    d = p.shape[0]
    with jax.named_scope("mrc.draw"):
        u = _segment_candidates(shared_key, n_is, d)
        u_sel = jnp.take_along_axis(u, indices[seg_ids][None, :], axis=0)[0]
        return (u_sel < clip01(p)).astype(jnp.float32)


def decode_segments(
    shared_key: jax.Array, indices: jax.Array, p: jax.Array, seg_ids: jax.Array, *, n_is: int
) -> jax.Array:
    """Reconstruct the encoder-selected sample from segment indices: (d,)."""
    _validate_seg_ids(seg_ids)
    return _decode_segments(shared_key, indices, p, seg_ids, n_is=n_is)


def receive_segments(
    shared_key: jax.Array, indices: jax.Array, p: jax.Array, seg_ids: jax.Array, *, n_is: int
) -> jax.Array:
    """Decode n_samples relayed segment-index vectors: (n_samples, n_seg) -> (d,)."""
    samples = jax.vmap(
        lambda ell, idx: decode_segments(sample_key(shared_key, ell), idx, p, seg_ids, n_is=n_is)
    )(jnp.arange(indices.shape[0]), indices)
    return jnp.mean(samples, axis=0)


def transmit_segments(
    shared_key, select_key, q, p, seg_ids, *, n_is: int, n_seg: int,
    n_samples: int = 1, seg_logw_fn: Optional[SegLogWFn] = None,
):
    def one(ell):
        res = encode_segments(
            sample_key(shared_key, ell), sample_key(select_key, ell), q, p, seg_ids,
            n_is=n_is, n_seg=n_seg, seg_logw_fn=seg_logw_fn,
        )
        return res.indices, res.sample

    idxs, samples = jax.lax.map(one, jnp.arange(n_samples))
    return idxs, jnp.mean(samples, axis=0)
