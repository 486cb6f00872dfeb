"""Pallas TPU kernel: flash attention (lazy-softmax, VMEM-resident tiles).

Motivation (EXPERIMENTS.md §Perf, qwen3-14b x train_4k): the XLA lowering of
chunked attention materialises the (Sq, C) score tensor ~8 times per chunk
(where -> max -> exp -> correction -> PV), ~35% of the step's HBM traffic.
On TPU the fix is the canonical flash kernel: scores live in VMEM tiles and
never reach HBM; per-row (max, denominator) run in f32 scratch.

Layout: inputs are pre-flattened to (BH, S, Dh) (GQA kv heads repeated by
the ops.py wrapper).  Grid (BH, Sq/BQ, Skv/BK); the kv axis is the innermost
(sequential) grid dim, accumulating into VMEM scratch; the output tile is
written on the last kv step.

VMEM working set per step: q,k,v tiles 3*128*128*4 + acc 128*128*4 + m/l
2*128*4 ~ 256 KiB << 16 MiB.  MXU dims are 128-aligned by ops.py padding.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BQ = 128   # query-row tile
BK = 128   # kv-row tile
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  causal: bool, window: int, scale: float, skv: int, nk: int):
    ki = pl.program_id(2)
    qi = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32) * scale          # (BQ, Dh)
    k = k_ref[0].astype(jnp.float32)                  # (BK, Dh)
    v = v_ref[0].astype(jnp.float32)

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (BQ, BK)

    qpos = qi * BQ + jax.lax.broadcasted_iota(jnp.int32, (BQ, BK), 0)
    kpos = ki * BK + jax.lax.broadcasted_iota(jnp.int32, (BQ, BK), 1)
    mask = kpos < skv                                  # non-pad
    if causal:
        mask = mask & (kpos <= qpos)
    if window > 0:
        mask = mask & (kpos > qpos - window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=1)
    acc_scr[...] = acc_scr[...] * corr[:, None] + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "scale",
                                             "skv", "interpret"))
def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool, window: int, scale: float,
                           skv: int, interpret: bool) -> jax.Array:
    """q: (BH, Sq, Dh); k, v: (BH, Skv_pad, Dh); 128-aligned shapes.

    ``skv`` is the unpadded kv length (mask boundary).  Use
    ``ops.flash_attention`` for the general-shape entry point.
    """
    bh, sq, dh = q.shape
    skv_pad = k.shape[1]
    if sq % BQ != 0 or skv_pad % BK != 0 or dh % 128 != 0:
        raise ValueError(
            f"flash_attention_pallas needs Sq % {BQ} == 0, Skv_pad % {BK} "
            f"== 0 and Dh % 128 == 0, got Sq={sq}, Skv_pad={skv_pad}, "
            f"Dh={dh} (use ops.flash_attention for the padded entry point)")
    nq, nk = sq // BQ, skv_pad // BK
    grid = (bh, nq, nk)
    kernel = functools.partial(_flash_kernel, causal=causal, window=window,
                               scale=scale, skv=skv, nk=nk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, BQ, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, BK, dh), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, BK, dh), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, BQ, dh), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, dh), q.dtype),
        scratch_shapes=[
            # f32 running max / denominator / accumulator in VMEM
            pltpu.VMEM((BQ,), jnp.float32),
            pltpu.VMEM((BQ,), jnp.float32),
            pltpu.VMEM((BQ, dh), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
