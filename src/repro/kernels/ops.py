"""JIT'd general-shape wrappers around the Pallas kernels.

These pad arbitrary shapes to the kernels' tile alignment, invoke the
kernel, and slice the result back.  ``interpret`` is derived from the
backend here, in one place (:func:`resolve_interpret`): the kernels run
in the Pallas interpreter on the CPU, where they are validated, and
compiled everywhere else.  Passing ``interpret=True`` / ``False`` pins
the mode (tests validate in interpret mode; compile rehearsals for a
described TPU pass ``False`` from a CPU process).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .bernoulli_kl import (ROW_TILE as KL_ROW_TILE, TILE_S as KL_TILE_S,
                           bernoulli_kl_pallas)
from .mrc_weights import TILE_I, TILE_S, mrc_logw_pallas
from .segment_logw import (NSEG_LANE, TILE_D, TILE_I as SEG_TILE_I,
                           segment_logw_pallas)


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``None`` -> interpret only on the CPU backend."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return interpret


def _pad_axis(x: jax.Array, axis: int, mult: int, value: float = 0.0) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _pad_rows(x: jax.Array, axis: int, tile: int, value: float = 0.0):
    """Pad a row axis to the kernels' row tiling: a multiple of 8 up to
    ``tile`` rows (one block holds them all), a multiple of ``tile``
    above it."""
    mult = 8 if x.shape[axis] <= tile else tile
    return _pad_axis(x, axis, mult, value)


@functools.partial(jax.jit, static_argnames=("interpret",))
def mrc_logw(x: jax.Array, a: jax.Array, b: jax.Array, *,
             interpret: Optional[bool] = None):
    """logW = X @ a + sum(b); x (NB, NIS, S), a/b (NB, S) -> (NB, NIS).

    Zero-padding is exact: padded entries contribute x*0 + 0 to the sums.
    Drop-in replacement for ``repro.core.mrc.default_logw`` (as ``logw_fn``).
    """
    nis = x.shape[1]
    xp = _pad_axis(_pad_rows(x.astype(jnp.float32), 1, TILE_I), 2, TILE_S)
    ap = _pad_axis(a.astype(jnp.float32), 1, TILE_S)
    bp = _pad_axis(b.astype(jnp.float32), 1, TILE_S)
    out = mrc_logw_pallas(xp, ap, bp, interpret=resolve_interpret(interpret))
    return out[:, :nis]


def _kl_colsums(q: jax.Array, p: jax.Array, interpret) -> jax.Array:
    """Column sums of KL(q||p) over (R, S) -> (S_padded,).

    Pads with q == p == 0.5 (zero KL), so the padded sums are exact.
    """
    qp = _pad_axis(_pad_rows(q.astype(jnp.float32), 0, KL_ROW_TILE, 0.5),
                   1, KL_TILE_S, 0.5)
    pp = _pad_axis(_pad_rows(p.astype(jnp.float32), 0, KL_ROW_TILE, 0.5),
                   1, KL_TILE_S, 0.5)
    return bernoulli_kl_pallas(qp, pp, interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def bernoulli_kl(q: jax.Array, p: jax.Array, *,
                 interpret: Optional[bool] = None):
    """Per-block KL(q||p) sums; q, p (NB, S) -> (NB,) nats.

    Row sums, as the column sums of the transposed blocks.
    """
    return _kl_colsums(q.T, p.T, interpret)[:q.shape[0]]


@functools.partial(jax.jit, static_argnames=("n_seg", "interpret"))
def segment_logw(u: jax.Array, p: jax.Array, a: jax.Array, b: jax.Array,
                 seg_ids: jax.Array, *, n_seg: int,
                 interpret: Optional[bool] = None):
    """Segment MRC log-weights; u (NIS, D), p/a/b/seg_ids (D,) -> (NIS, n_seg).

    Drop-in replacement for ``repro.core.mrc.default_segment_logw`` (as
    ``seg_logw_fn``); ``seg_ids`` must be non-decreasing (the segment
    codec's contract).  Padding contract: padded ``u`` entries carry 1.0
    against a padded prior of 0.0 (the compare is strictly ``u < p``, so
    they never select), padded ``a``/``b`` are 0 and padded ``seg_ids``
    repeat the last id (keeping them non-decreasing) -- every pad
    contributes exactly 0 to its segment sum, and the padded candidate
    rows / segment columns are sliced off.
    """
    nis = u.shape[0]
    up = _pad_axis(_pad_rows(u.astype(jnp.float32), 0, SEG_TILE_I, 1.0),
                   1, TILE_D, value=1.0)
    pp = _pad_axis(p.astype(jnp.float32)[None], 1, TILE_D)
    ap = _pad_axis(a.astype(jnp.float32)[None], 1, TILE_D)
    bp = _pad_axis(b.astype(jnp.float32)[None], 1, TILE_D)
    seg = seg_ids.astype(jnp.int32)[None]
    sp = jnp.pad(seg, ((0, 0), (0, (-seg.shape[1]) % TILE_D)), mode="edge")
    nseg_pad = n_seg + (-n_seg) % NSEG_LANE
    out = segment_logw_pallas(up, pp, ap, bp, sp, n_seg=nseg_pad,
                              interpret=resolve_interpret(interpret))
    return out[:nis, :n_seg]


@functools.partial(jax.jit, static_argnames=("interpret",))
def bernoulli_kl_total(q: jax.Array, p: jax.Array, *,
                       interpret: Optional[bool] = None):
    """Mean-over-clients total KL(q||p): q, p (n, d) -> f32 scalar (nats).

    The per-parameter sums over clients run through the Pallas streaming
    reduction (``bernoulli_kl_pallas``); pads carry q == p == 0.5 (zero
    KL), so the padded result is exact.  This is the on-device statistic
    the fused engine feeds ``AdaptiveAvgAllocation`` -- mean_i sum_e KL
    equals sum_e mean_i KL, which is what the host control plane computed
    from numpy.
    """
    return jnp.sum(_kl_colsums(q, p, interpret)) / q.shape[0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def bernoulli_kl_profile(q: jax.Array, p: jax.Array, *,
                         interpret: Optional[bool] = None):
    """Per-parameter cohort-mean KL(q||p): q, p (n, d) -> (d,) nats.

    The client axis is the kernel's reduction (row) axis; pads carry
    q == p == 0.5 (zero KL), so dividing the padded column sums by the
    true cohort size recovers the mean.  This is the on-device profile
    statistic the fused engine feeds ``AdaptiveAllocation`` (matching
    ``jnp.mean(vmap(bern_kl), axis=0)`` up to f32 summation order).
    """
    n, d = q.shape
    return _kl_colsums(q, p, interpret)[:d] / n


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    scale: float = 1.0,
                    interpret: Optional[bool] = None) -> jax.Array:
    """General-shape flash attention.

    q: (B, Sq, H, Dh); k, v: (B, Skv, Hkv, Dh) -- GQA kv heads are repeated,
    heads fold into the batch dim, Sq/Skv/Dh pad to the kernel tiles.
    Returns (B, Sq, H, Dh).
    """
    from .flash_attn import BK, BQ, flash_attention_pallas
    b, sq, h, dh = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    rep = h // hkv
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    # (B, S, H, Dh) -> (B*H, S, Dh)
    qf = jnp.moveaxis(q, 2, 1).reshape(b * h, sq, dh)
    kf = jnp.moveaxis(k, 2, 1).reshape(b * h, skv, dh)
    vf = jnp.moveaxis(v, 2, 1).reshape(b * h, skv, dh)
    qp = _pad_axis(_pad_axis(qf, 1, BQ), 2, 128)
    kp = _pad_axis(_pad_axis(kf, 1, BK), 2, 128)
    vp = _pad_axis(_pad_axis(vf, 1, BK), 2, 128)
    out = flash_attention_pallas(qp, kp, vp, causal=causal, window=window,
                                 scale=scale, skv=skv,
                                 interpret=resolve_interpret(interpret))
    out = out[:, :sq, :dh].reshape(b, h, sq, dh)
    return jnp.moveaxis(out, 1, 2)


def rwkv_time_mix(r: jax.Array, k: jax.Array, v: jax.Array, logw: jax.Array,
                  u: jax.Array, *,
                  interpret: Optional[bool] = None) -> jax.Array:
    """General-shape chunked RWKV-6 time-mix (zero initial state).

    r/k/v/logw: (B, S, H, Dh); u: (H, Dh).  Returns (B, S, H, Dh).
    Sequence pads to the kernel chunk; heads fold into the batch dim.
    """
    from .rwkv_chunk import CHUNK, rwkv_chunk_pallas
    b, s, h, dh = r.shape

    def fold(t):  # (B, S, H, Dh) -> (B*H, S_pad, Dh)
        t = jnp.moveaxis(t, 2, 1).reshape(b * h, s, dh)
        return _pad_axis(t, 1, CHUNK)

    # pad value 0 is safe: logw 0 => decay 1, r/k/v 0 contribute nothing
    rf, kf, vf, lwf = fold(r), fold(k), fold(v), fold(logw)
    uf = jnp.tile(u[None], (b, 1, 1)).reshape(b * h, 1, dh)
    out = rwkv_chunk_pallas(rf, kf, vf, lwf, uf,
                            interpret=resolve_interpret(interpret))
    out = out[:, :s].reshape(b, h, s, dh)
    return jnp.moveaxis(out, 1, 2)


@functools.lru_cache(maxsize=None)
def mrc_logw_fn(interpret: Optional[bool] = None):
    """Return a ``logw_fn`` closure for ``repro.core.mrc.encode_fixed``.

    ``interpret=None`` resolves from the backend when the encoder traces.
    Cached per ``interpret`` value: ``encode_fixed`` treats ``logw_fn`` as
    a static jit argument (hashed by identity), so handing out a fresh
    closure per call would force a retrace per channel construction.
    """
    def fn(x, a, b):
        return mrc_logw(x, a, b, interpret=interpret)
    return fn


@functools.lru_cache(maxsize=None)
def segment_logw_fn(interpret: Optional[bool] = None):
    """Return a ``seg_logw_fn`` closure for ``repro.core.mrc.encode_segments``.

    Cached per ``interpret`` value for the same reason as ``mrc_logw_fn``:
    the encoder treats the hook as a static jit argument hashed by
    identity, so a fresh closure per call would retrace.
    """
    def fn(u, p, a, b, seg_ids, n_seg):
        return segment_logw(u, p, a, b, seg_ids, n_seg=n_seg,
                            interpret=interpret)
    return fn
