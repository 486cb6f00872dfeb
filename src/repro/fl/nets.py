"""Pure-JAX classifier networks for the FL experiments.

Bias-free families: CNNs mirroring the paper's LeNet5 / 4CNN / 6CNN
(scaled to the synthetic datasets), MLPs, and the Vision Transformer of
Dosovitskiy et al. (2020) (``make_vit``: pre-LayerNorm blocks without
affine parameters).  Every builder returns a :class:`Net` whose weights are
a flat list of arrays, so ``ravel_pytree`` gives the flat parameter vector
the FL channels carry.  For probabilistic-mask training the weights use the
*signed-constant* initialization of Ramanujan et al. (2020):
w = sign(n) * std_kaiming -- the setting in which random subnetworks are
known to be expressive.
"""
from __future__ import annotations

import math
from typing import Callable, List, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree


class Net(NamedTuple):
    init: Callable[[jax.Array], list]
    apply: Callable[[list, jax.Array], jax.Array]  # (weights, x NHWC) -> logits
    # ``apply`` reads each sample as one flat row (``make_mlp``): local
    # training then fetches its batches as rows of a (shard, features) copy
    reads_rows: bool = False


def _conv(x, w):
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def _maxpool(x):
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID"
    )


def _normal(key, shape, std: float, signed_constant: bool):
    w = jax.random.normal(key, shape)
    if signed_constant:
        return jnp.sign(w) * std
    return w * std


def _kaiming_signed(key, shape, fan_in, signed_constant: bool):
    return _normal(key, shape, math.sqrt(2.0 / fan_in), signed_constant)


def make_cnn(
    hw: int = 14,
    channels: int = 1,
    n_classes: int = 10,
    conv_widths: Sequence[int] = (32, 64),
    dense_widths: Sequence[int] = (128,),
    signed_constant: bool = False,
) -> Net:
    """Conv(3x3)+ReLU+MaxPool blocks, then dense head. Bias-free."""
    n_pools = len(conv_widths)
    final_hw = hw // (2 ** n_pools)
    assert final_hw >= 1, "too many pools for input size"

    shapes: List[Tuple[Tuple[int, ...], int]] = []  # (shape, fan_in)
    cin = channels
    for w_ in conv_widths:
        shapes.append(((3, 3, cin, w_), 3 * 3 * cin))
        cin = w_
    flat = final_hw * final_hw * cin
    din = flat
    for w_ in dense_widths:
        shapes.append(((din, w_), din))
        din = w_
    shapes.append(((din, n_classes), din))

    def init(key):
        keys = jax.random.split(key, len(shapes))
        return [_kaiming_signed(k, s, f, signed_constant) for k, (s, f) in zip(keys, shapes)]

    n_conv = len(conv_widths)

    def apply(weights, x):
        h = x
        for i in range(n_conv):
            h = _maxpool(jax.nn.relu(_conv(h, weights[i])))
        h = h.reshape(h.shape[0], -1)
        for w_ in weights[n_conv:-1]:
            h = jax.nn.relu(h @ w_)
        return h @ weights[-1]

    return Net(init=init, apply=apply)


MXU_DEPTH = 128  # inputs a TPU matrix unit sums in one pass


def ordered_dot(x: jax.Array, w: jax.Array) -> jax.Array:
    """``x @ w`` summed over its inputs in a fixed order: two halves, each
    in ``MXU_DEPTH``-wide pieces from its start, added one after another.

    XLA's TPU emitter sums a 784-input layer in this order when its weights
    stream from HBM in two windows, as they do in a program that gathers
    its batches as images from a sample-minor copy (the benchmark's plain
    reference).  Given rows, the compiler keeps the weights in VMEM and
    sums in one window instead; that rounds differently, and a local
    training run of a thousand Adam steps carries the difference into the
    model.  Written out, the order no longer rests on where the compiler
    places the weights."""
    k = w.shape[0]
    half = k // 2
    cuts = sorted({0, half, k, *range(MXU_DEPTH, half, MXU_DEPTH),
                   *range(half + MXU_DEPTH, k, MXU_DEPTH)})
    out = None
    for a, b in zip(cuts[:-1], cuts[1:]):
        part = x[:, a:b] @ w[a:b]
        out = part if out is None else out + part
    return out


def make_mlp(
    in_dim: int, widths: Sequence[int] = (256, 256), n_classes: int = 10,
    signed_constant: bool = False,
) -> Net:
    dims = [in_dim, *widths, n_classes]

    def init(key):
        keys = jax.random.split(key, len(dims) - 1)
        return [
            _kaiming_signed(k, (a, b), a, signed_constant)
            for k, a, b in zip(keys, dims[:-1], dims[1:])
        ]

    def apply(weights, x):
        h = x.reshape(x.shape[0], -1)
        h = jax.nn.relu(ordered_dot(h, weights[0]))
        for w_ in weights[1:-1]:
            h = jax.nn.relu(h @ w_)
        return h @ weights[-1]

    return Net(init=init, apply=apply, reads_rows=True)


VIT_EMBED_STD = 0.02  # class token and position embeddings


def _layer_norm(x, eps: float = 1e-6):
    """LayerNorm over the last axis, without scale or shift."""
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps)


def make_vit(
    hw: int = 224, channels: int = 3, patch: int = 16, width: int = 768,
    depth: int = 12, heads: int = 12, mlp_width: int = 3072,
    n_classes: int = 10, signed_constant: bool = False,
) -> Net:
    """Vision Transformer (Dosovitskiy et al. 2020), bias-free.

    Weights, in order: the patch embedding (patch*patch*channels, width),
    the class token (width,), the position embeddings (tokens, width), then
    per block qkv (width, 3*width), out (width, width), fc1 (width,
    mlp_width), fc2 (mlp_width, width), and the head (width, n_classes).
    Pre-LayerNorm blocks (no affine parameters, eps 1e-6): softmax
    self-attention over all tokens and an exact-GELU MLP, each on a
    residual; a final LayerNorm and the linear head on the class token.
    Matrices take Kaiming init, the embeddings ``VIT_EMBED_STD``.
    """
    if hw % patch or width % heads:
        raise ValueError(f"hw={hw} not a multiple of patch={patch}, or "
                         f"width={width} not a multiple of heads={heads}")
    grid = hw // patch
    tokens = grid * grid + 1
    pdim = patch * patch * channels
    hd = width // heads
    shapes: List[Tuple[Tuple[int, ...], float]] = [  # (shape, init std)
        ((pdim, width), math.sqrt(2.0 / pdim)),
        ((width,), VIT_EMBED_STD),
        ((tokens, width), VIT_EMBED_STD)]
    for _ in range(depth):
        shapes += [((width, 3 * width), math.sqrt(2.0 / width)),
                   ((width, width), math.sqrt(2.0 / width)),
                   ((width, mlp_width), math.sqrt(2.0 / width)),
                   ((mlp_width, width), math.sqrt(2.0 / mlp_width))]
    shapes.append(((width, n_classes), math.sqrt(2.0 / width)))

    def init(key):
        keys = jax.random.split(key, len(shapes))
        return [_normal(k, s, std, signed_constant)
                for k, (s, std) in zip(keys, shapes)]

    def attention(h, w_qkv, w_out):
        b = h.shape[0]
        qkv = (_layer_norm(h) @ w_qkv).reshape(b, tokens, 3, heads, hd)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (1.0 / math.sqrt(hd))
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
        return o.reshape(b, tokens, width) @ w_out

    def apply(weights, x):
        b = x.shape[0]
        with jax.named_scope("vit.patch"):
            p = x.reshape(b, grid, patch, grid, patch, channels)
            p = p.transpose(0, 1, 3, 2, 4, 5).reshape(b, grid * grid, pdim)
            cls = jnp.broadcast_to(weights[1], (b, 1, width))
            h = jnp.concatenate([cls, p @ weights[0]], axis=1) + weights[2]
        for i in range(depth):
            w_qkv, w_out, w_fc1, w_fc2 = weights[3 + 4 * i: 7 + 4 * i]
            with jax.named_scope("vit.attn"):
                h = h + attention(h, w_qkv, w_out)
            with jax.named_scope("vit.mlp"):
                y = jax.nn.gelu(_layer_norm(h) @ w_fc1, approximate=False)
                h = h + y @ w_fc2
        with jax.named_scope("vit.head"):
            return _layer_norm(h[:, 0]) @ weights[-1]

    return Net(init=init, apply=apply)


def flatten_weights(weights) -> Tuple[jax.Array, Callable]:
    return ravel_pytree(weights)


def cross_entropy(logits: jax.Array, y: jax.Array) -> jax.Array:
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))


def accuracy(apply_fn, weights, x, y, batch: int = 1000) -> jax.Array:
    """Mean top-1 accuracy as a float32 scalar array.

    Fully traceable (no host round-trips), so ``task.evaluate`` can run
    under ``lax.cond`` inside the engine's fused round scan.  Large test
    sets are processed in ``batch``-row chunks via ``lax.map`` so the
    logits tensor never exceeds one chunk.
    """
    n = x.shape[0]
    if n <= batch:
        correct = jnp.sum(
            (jnp.argmax(apply_fn(weights, x), -1) == y).astype(jnp.float32))
        return correct * jnp.float32(1.0 / n)
    nb = -(-n // batch)
    pad = nb * batch - n
    xp = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
    yp = jnp.pad(y, (0, pad), constant_values=-1)  # -1 never equals an argmax

    def chunk(i):
        xi = jax.lax.dynamic_slice_in_dim(xp, i * batch, batch)
        yi = jax.lax.dynamic_slice_in_dim(yp, i * batch, batch)
        return jnp.sum(
            (jnp.argmax(apply_fn(weights, xi), -1) == yi).astype(jnp.float32))

    # Multiply by the reciprocal instead of dividing: XLA rewrites a
    # divide-by-constant to a reciprocal multiply in *some* programs, so an
    # explicit mul is the only form that rounds identically inside the
    # engine's fused scan and in the standalone host-loop eval.
    return jnp.sum(jax.lax.map(chunk, jnp.arange(nb))) * jnp.float32(1.0 / n)
