"""Pallas TPU kernel: chunked RWKV-6 time-mix (decay-weighted linear attn).

EXPERIMENTS.md §Perf pair 1 ends with the XLA chunked closed form 25x off
the compute roofline because the pairwise-decay tensor and the chunk
streams still round-trip HBM.  This kernel is the TPU-native step: one
grid cell processes one (batch*head, chunk) tile with the (Dh, Dh) state
carried in VMEM f32 scratch across the (sequential) chunk axis -- state,
scores and decay tiles never reach HBM.

Math per chunk (c = cumsum(log w), all <= 0):

    o_t   = (r_t . e^{c_{t-1}}) S  +  sum_{s<t} (r_t k_s e^{c_{t-1}-c_s}) v_s
            + (r_t . u . k_t) v_t
    S'    = e^{c_C} . S + sum_s (k_s e^{c_C - c_s}) v_s^T

Exactly the math of ``models.rwkv6._time_mix_chunked`` (tested against it
and the per-token reference).  Grid: (BH, S/C) with the chunk axis
innermost/sequential; tiles (C, Dh) with C = Dh = 64 (one VREG-friendly
square; VMEM per step ~ 4 * 64*64*4 + dmat 64*64*64*4 ~ 1.1 MiB).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 64


def _rwkv_chunk_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, o_ref, s_scr, *,
                       n_chunks: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    r = r_ref[0].astype(jnp.float32)       # (C, Dh)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    lw = lw_ref[0].astype(jnp.float32)     # log decay, <= 0
    u = u_ref[0].astype(jnp.float32)       # (1, Dh) bonus row

    cum = jnp.cumsum(lw, axis=0)           # c_t inclusive
    cum_prev = cum - lw                    # c_{t-1}

    s_in = s_scr[...]
    o_inter = jnp.dot(r * jnp.exp(cum_prev), s_in,
                      preferred_element_type=jnp.float32)      # (C, Dh)

    # pairwise decay exp(c_{t-1} - c_s) for s < t, per channel
    diff = cum_prev[:, None, :] - cum[None, :, :]              # (C, C, Dh)
    dmat = jnp.exp(jnp.minimum(diff, 0.0))
    p = jnp.einsum("tk,sk,tsk->ts", r, k, dmat)                # (C, C)
    c = r.shape[0]
    ti = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    p = jnp.where(si < ti, p, 0.0)
    o_intra = jnp.dot(p, v, preferred_element_type=jnp.float32)

    o_diag = jnp.sum(r * u * k, axis=1, keepdims=True) * v

    o_ref[0] = (o_inter + o_intra + o_diag).astype(o_ref.dtype)

    decay_to_end = jnp.exp(cum[-1:] - cum)                     # (C, Dh)
    s_scr[...] = jnp.exp(cum[-1])[:, None] * s_in + jnp.dot(
        (k * decay_to_end).T, v, preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def rwkv_chunk_pallas(r: jax.Array, k: jax.Array, v: jax.Array,
                      logw: jax.Array, u: jax.Array, *,
                      interpret: bool) -> jax.Array:
    """r/k/v/logw: (BH, S, Dh) with S % CHUNK == 0; u: (BH, 1, Dh).

    Returns the time-mix output (BH, S, Dh); zero initial state.  Use
    ``ops.rwkv_time_mix`` for the general-shape entry point.
    """
    bh, s, dh = r.shape
    if s % CHUNK != 0:
        raise ValueError(
            f"rwkv_chunk_pallas needs S % {CHUNK} == 0, got S={s} "
            "(use ops.rwkv_time_mix for the padded general-shape entry point)")
    n_chunks = s // CHUNK
    grid = (bh, n_chunks)
    kernel = functools.partial(_rwkv_chunk_kernel, n_chunks=n_chunks)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, CHUNK, dh), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, CHUNK, dh), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, CHUNK, dh), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, CHUNK, dh), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, 1, dh), lambda b, c: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, CHUNK, dh), lambda b, c: (b, c, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, dh), r.dtype),
        scratch_shapes=[pltpu.VMEM((dh, dh), jnp.float32)],
        interpret=interpret,
    )(r, k, v, logw, u)
