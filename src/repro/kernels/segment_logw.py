"""Pallas TPU kernel: segment MRC importance log-weights.

The adaptive (variable-block) codec evaluates

    logW[i, s] = sum_{e in seg s} x_{ie} * a_e  +  sum_{e in seg s} b_e

for every candidate row i and segment s.  The jnp route
(``core.mrc.default_segment_logw``) materialises the full (n_is, d)
``xa = where(u < p, a, 0)`` tensor in HBM and runs a vmapped
``segment_sum``.  Here the candidate uniforms stream through VMEM once:
each (TI, TILE_D) tile of ``u`` is compared against the prior row and
selected against ``a`` in registers, the candidate-independent prior term
``b`` is added per element, and the tile is reduced per segment on the MXU
against a one-hot segment matrix

    M^T[s, e] = (seg_ids[e] == j * NSEG_LANE + s)     (NSEG_LANE, TILE_D)

for one 128-wide window j of segments, so the partial is ``xab @ M`` (a
masked sum at f32, ``Precision.HIGHEST``; M is 0/1).

Segment tiling.  ``seg_ids`` are non-decreasing (the codec contract the
wire layer enforces), so d-tile k touches a contiguous run of segment
windows, and a window's d-tiles are contiguous too.  The grid walks one
monotone staircase path through the (d-tile, window) plane: from (0, 0)
to (K-1, J-1), each step advancing either the d-tile or the window, so
it has exactly K + J - 1 steps and visits every window.  The step's pair
rides in as scalar-prefetched tables (``kk``, ``jj``); the input blocks
follow ``kk`` and the (TI, NSEG_LANE) output block follows ``jj``.  Each
window is visited in one run of consecutive steps, so its output block
stays resident in VMEM while it accumulates and is written back once.
VMEM holds one (TI, TILE_D) tile, the (NSEG_LANE, TILE_D) one-hot and one
(TI, NSEG_LANE) output block, whatever the segment count -- which is what
lets the kernel compile at every bucket of the adaptive grid (up to
d // 8 segments), where a whole-row (TI, n_seg) accumulator ran out of
VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE_I = 128     # largest candidate-row tile
TILE_D = 512     # parameter tile
NSEG_LANE = 128  # segment window width (the lane width)

_NT = (((1,), (1,)), ((), ()))  # contract the last dims: A @ B^T


def _segment_logw_kernel(kk_ref, jj_ref, u_ref, p_ref, a_ref, b_ref,
                         seg_ref, o_ref):
    """One (i_tile, path step) grid step."""
    del kk_ref  # consumed by the index maps
    t = pl.program_id(1)
    j = jj_ref[t]
    first = jnp.logical_or(t == 0, j != jj_ref[jnp.maximum(t - 1, 0)])

    seg = seg_ref[...]                                       # (1, TILE_D)
    window = jax.lax.broadcasted_iota(
        jnp.int32, (NSEG_LANE, seg.shape[1]), 0) + j * NSEG_LANE
    onehot_t = (window == seg).astype(jnp.float32)           # (128, TILE_D)
    # Fused compare + select (x is {0,1}, so x*a == where(u < p, a, 0)),
    # with the prior term riding along per element.
    xab = jnp.where(u_ref[...] < p_ref[...], a_ref[...], 0.0) + b_ref[...]
    part = jax.lax.dot_general(xab, onehot_t, _NT,
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)

    @pl.when(first)
    def _init():
        o_ref[...] = part

    @pl.when(jnp.logical_not(first))
    def _acc():
        o_ref[...] = o_ref[...] + part


def _staircase(seg_ids: jax.Array, n_seg: int):
    """The grid's (d-tile, segment-window) path: int32 tables (K+J-1,).

    ``seg_ids`` (1, D) non-decreasing.  Step t sits at d-tile kk[t] and
    window jj[t] = t - kk[t]; d-tile k ends at window e_k (the window of
    its last id), so its steps are t in [k + e_{k-1}, k + e_k].
    """
    n_k = seg_ids.shape[1] // TILE_D
    n_j = n_seg // NSEG_LANE
    last = seg_ids[0, TILE_D - 1::TILE_D] // NSEG_LANE
    ends = jnp.arange(n_k, dtype=jnp.int32) + jnp.clip(last, 0, n_j - 1)
    t = jnp.arange(n_k + n_j - 1, dtype=jnp.int32)
    kk = jnp.minimum(jnp.searchsorted(ends, t, side="left"),
                     n_k - 1).astype(jnp.int32)
    return kk, t - kk


@functools.partial(jax.jit, static_argnames=("n_seg", "interpret"))
def segment_logw_pallas(u: jax.Array, p: jax.Array, a: jax.Array,
                        b: jax.Array, seg_ids: jax.Array, *, n_seg: int,
                        interpret: bool) -> jax.Array:
    """Per-segment importance log-weights for tile-aligned shapes.

    u: (NIS, D) uniforms; p, a, b: (1, D) f32; seg_ids: (1, D) int32,
    non-decreasing.  Ids outside [0, n_seg) contribute nothing (as in
    ``segment_sum``).  Returns (NIS, n_seg) f32.  Requires NIS % 8 == 0
    (NIS % TILE_I == 0 above TILE_I), D % TILE_D == 0 and
    n_seg % NSEG_LANE == 0 (use ``ops.segment_logw`` for general shapes).
    """
    nis, d = u.shape
    ti = min(nis, TILE_I)
    if nis % 8 or nis % ti or d % TILE_D or n_seg % NSEG_LANE:
        raise ValueError(
            f"segment_logw_pallas needs NIS % 8 == 0 (NIS % {TILE_I} == 0 "
            f"above {TILE_I}), D % {TILE_D} == 0 and n_seg % {NSEG_LANE} "
            f"== 0; got NIS={nis}, D={d}, n_seg={n_seg} (use "
            "ops.segment_logw for general shapes)")
    kk, jj = _staircase(seg_ids, n_seg)
    row = pl.BlockSpec((1, TILE_D), lambda i, t, kk, jj: (0, kk[t]))
    return pl.pallas_call(
        _segment_logw_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nis // ti, kk.shape[0]),
            in_specs=[
                pl.BlockSpec((ti, TILE_D), lambda i, t, kk, jj: (i, kk[t])),
                row, row, row, row,
            ],
            out_specs=pl.BlockSpec((ti, NSEG_LANE),
                                   lambda i, t, kk, jj: (i, jj[t])),
        ),
        out_shape=jax.ShapeDtypeStruct((nis, n_seg), jnp.float32),
        interpret=interpret,
    )(kk, jj, u, p, a, b, seg_ids)
