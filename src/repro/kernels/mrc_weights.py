"""Pallas TPU kernel: MRC importance log-weights as an MXU matvec.

The per-block MRC weight evaluation

    logW[i] = sum_e  x_{ie} * a_e + b_e          (i in [n_IS])

is the compute hot-spot of BiCompFL encoding: every round, every client
evaluates it for every block (d * n_IS multiply-adds total).  Refactored as

    logW = a @ X^T + sum(b)

it is a (1 x S) x (S x n_IS) product on the MXU.  Candidates X live in HBM
as (NB, NIS, S); (TI, TS=128) tiles stream through VMEM, partial dot
products accumulate in the f32 output block, and the offset term
sum_s b[nb, s] folds in per S-tile.  Grid: (NB, NIS/TI, S/TS); the output
BlockSpec maps all S-tiles of one (nb, i-tile) to the same VMEM block, so
the accumulation is carried in VMEM without HBM round-trips.

Tiling: ``a``/``b`` ride as (NB, 1, S) and the output as (NB, 1, NIS), so
every block's last two dims are either (1, 128k) with the 1 equal to the
array's own unit axis, or (TI, 128) with TI a multiple of 8 -- what the
TPU's (8, 128) layout accepts.  TI is the whole (8-padded) candidate axis
up to ``TILE_I`` and ``TILE_I`` above it.  The matvec runs at f32
(``Precision.HIGHEST``): ``a`` is not exact in bf16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE_I = 128  # largest candidate-row tile
TILE_S = 128  # block-entry tile (MXU lane dim)

_NT = (((1,), (1,)), ((), ()))  # contract the last dims: A @ B^T


def _mrc_logw_kernel(x_ref, a_ref, b_ref, o_ref):
    """One (nb, i_tile, s_tile) grid step."""
    s = pl.program_id(2)
    part = jax.lax.dot_general(a_ref[0], x_ref[0], _NT,
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)  # (1, TI)
    part = part + jnp.sum(b_ref[0])

    @pl.when(s == 0)
    def _init():
        o_ref[0] = part

    @pl.when(s != 0)
    def _acc():
        o_ref[0] = o_ref[0] + part


@functools.partial(jax.jit, static_argnames=("interpret",))
def mrc_logw_pallas(x: jax.Array, a: jax.Array, b: jax.Array, *,
                    interpret: bool):
    """logW = X @ a + sum(b) for tile-aligned shapes.

    x: (NB, NIS, S) float32 {0,1};  a, b: (NB, S);  returns (NB, NIS).
    Needs NIS % 8 == 0 (NIS % TILE_I == 0 above TILE_I) and
    S % TILE_S == 0 (use ``ops.mrc_logw`` for the padded general-shape
    entry point).
    """
    nb, nis, s = x.shape
    ti = min(nis, TILE_I)
    if nis % 8 or nis % ti or s % TILE_S:
        raise ValueError(
            f"mrc_logw_pallas needs NIS % 8 == 0 (NIS % {TILE_I} == 0 above "
            f"{TILE_I}) and S % {TILE_S} == 0, got NIS={nis}, S={s} (use "
            "ops.mrc_logw for the padded general-shape entry point)")
    out = pl.pallas_call(
        _mrc_logw_kernel,
        grid=(nb, nis // ti, s // TILE_S),
        in_specs=[
            pl.BlockSpec((1, ti, TILE_S), lambda b_, i, s_: (b_, i, s_)),
            pl.BlockSpec((1, 1, TILE_S), lambda b_, i, s_: (b_, 0, s_)),
            pl.BlockSpec((1, 1, TILE_S), lambda b_, i, s_: (b_, 0, s_)),
        ],
        out_specs=pl.BlockSpec((1, 1, ti), lambda b_, i, s_: (b_, 0, i)),
        out_shape=jax.ShapeDtypeStruct((nb, 1, nis), jnp.float32),
        interpret=interpret,
    )(x, a[:, None, :], b[:, None, :])
    return out[:, 0, :]
