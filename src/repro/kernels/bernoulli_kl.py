"""Pallas TPU kernel: column-wise Bernoulli KL reduction.

Adaptive(-Avg) block allocation needs the elementwise KL d_KL(q_e || p_e)
of every client's posterior against its prior, reduced over the client
axis (the per-parameter cohort profile) or over everything (the mean
total).  This is a VPU-bound streaming reduction over an (R, S) array:
(TR, TILE_S) tiles of q and p flow through VMEM, each tile's KL folds onto
8 sublanes with plain vector adds (no cross-lane reduction in the loop),
and the (8, TILE_S) partial sums accumulate in the output block across
the row-grid dimension.  The wrapper sums the 8 sublanes, giving the
column sums (S,).  Row sums (per-block KL) are column sums of the
transpose (``ops.bernoulli_kl``).

Tiling: every block's last two dims are (8k, 512), which the TPU's
(8, 128) layout accepts; a row count above ``ROW_TILE`` is tiled by it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.bernoulli import bern_kl

TILE_S = 512     # lane tile
ROW_TILE = 256   # largest row tile
SUBLANES = 8


def _kl_kernel(q_ref, p_ref, o_ref):
    r = pl.program_id(1)
    kl = bern_kl(q_ref[...], p_ref[...])  # the jnp route's own expression
    part = kl[0:SUBLANES]
    for i in range(1, kl.shape[0] // SUBLANES):
        part = part + kl[i * SUBLANES:(i + 1) * SUBLANES]

    @pl.when(r == 0)
    def _init():
        o_ref[...] = part

    @pl.when(r != 0)
    def _acc():
        o_ref[...] = o_ref[...] + part


@functools.partial(jax.jit, static_argnames=("interpret",))
def bernoulli_kl_pallas(q: jax.Array, p: jax.Array, *, interpret: bool):
    """Column sums of the elementwise KL(q||p): (R, S) f32 -> (S,).

    Needs R % 8 == 0, R % ROW_TILE == 0 when R > ROW_TILE, and
    S % TILE_S == 0 (``ops`` pads with q == p == 0.5, which is zero KL).
    """
    r, s = q.shape
    tr = min(r, ROW_TILE)
    if r % SUBLANES or r % tr or s % TILE_S:
        raise ValueError(
            f"bernoulli_kl_pallas needs R % {SUBLANES} == 0 (R % {ROW_TILE} "
            f"== 0 above {ROW_TILE}) and S % {TILE_S} == 0, got R={r}, S={s} "
            "(use ops.bernoulli_kl* for the padded general-shape entry points)")
    out = pl.pallas_call(
        _kl_kernel,
        grid=(s // TILE_S, r // tr),
        in_specs=[
            pl.BlockSpec((tr, TILE_S), lambda c, r_: (r_, c)),
            pl.BlockSpec((tr, TILE_S), lambda c, r_: (r_, c)),
        ],
        out_specs=pl.BlockSpec((SUBLANES, TILE_S), lambda c, r_: (0, c)),
        out_shape=jax.ShapeDtypeStruct((SUBLANES, s), jnp.float32),
        interpret=interpret,
    )(q, p)
    return jnp.sum(out, axis=0)
