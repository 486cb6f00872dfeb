"""Plain reference of the Vision Transformer (Dosovitskiy et al. 2020,
arXiv:2010.11929, Sec. 3.1), bias-free as the program's ``make_vit``
builds it.  Arguments are those of that builder.

    z_0 = [x_class; x_p^1 E; ...; x_p^N E] + E_pos
    z'_l = MSA(LN(z_{l-1})) + z_{l-1}
    z_l = MLP(LN(z'_l)) + z'_l
    y = LN(z_L^0) W_head

LN has no scale or shift (eps 1e-6), no linear map has a bias, the MLP is
fc1, exact (erf) GELU, fc2, and MSA is softmax attention of ``heads``
heads over every token.  A patch is flattened in (row, column, channel)
order.  Matrices start from Kaiming's normal, std sqrt(2 / fan_in); the
class token and the position embeddings from a normal of std 0.02
(``fan_in`` None in ``layer_shapes``)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

EMBED_STD = 0.02
LN_EPS = 1e-6


def layer_shapes(hw=224, channels=3, patch=16, width=768, depth=12,
                 heads=12, mlp_width=3072, n_classes=10):
    """[(shape, fan_in)] in parameter order; fan_in None for the
    embeddings, which take ``EMBED_STD``."""
    tokens = (hw // patch) ** 2 + 1
    pdim = patch * patch * channels
    out = [((pdim, width), pdim), ((width,), None), ((tokens, width), None)]
    for _ in range(depth):
        out += [((width, 3 * width), width), ((width, width), width),
                ((width, mlp_width), width), ((mlp_width, width), mlp_width)]
    out.append(((width, n_classes), width))
    return out


def _ln(x):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    c = x - mean
    var = jnp.mean(c * c, axis=-1, keepdims=True)
    return c / jnp.sqrt(var + LN_EPS)


def _gelu(x):
    return 0.5 * x * (1.0 + jax.scipy.special.erf(x / math.sqrt(2.0)))


def make_apply(hw=224, channels=3, patch=16, width=768, depth=12, heads=12,
               mlp_width=3072, n_classes=10,
               precision=jax.lax.Precision.HIGHEST):
    g = hw // patch
    n_tok = g * g + 1
    hd = width // heads

    def dot(a, b):
        return jnp.matmul(a, b, precision=precision)

    def msa(z, w_qkv, w_out):
        n = z.shape[0]
        qkv = dot(_ln(z), w_qkv)                       # (n, T, 3W)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q, k, v = (t.reshape(n, n_tok, heads, hd).transpose(0, 2, 1, 3)
                   for t in (q, k, v))                 # (n, heads, T, hd)
        logits = jnp.einsum("nhqd,nhkd->nhqk", q, k,
                            precision=precision) / math.sqrt(hd)
        att = jax.nn.softmax(logits, axis=-1)
        o = jnp.einsum("nhqk,nhkd->nhqd", att, v, precision=precision)
        return dot(o.transpose(0, 2, 1, 3).reshape(n, n_tok, width), w_out)

    def apply(weights, x):
        n = x.shape[0]
        patches = x.reshape(n, g, patch, g, patch, channels)
        patches = patches.transpose(0, 1, 3, 2, 4, 5).reshape(
            n, g * g, patch * patch * channels)
        cls = jnp.tile(weights[1][None, None, :], (n, 1, 1))
        z = jnp.concatenate([cls, dot(patches, weights[0])], axis=1)
        z = z + weights[2][None]
        for layer in range(depth):
            w_qkv, w_out, w_fc1, w_fc2 = weights[3 + 4 * layer:
                                                 7 + 4 * layer]
            z = z + msa(z, w_qkv, w_out)
            z = z + dot(_gelu(dot(_ln(z), w_fc1)), w_fc2)
        return dot(_ln(z[:, 0, :]), weights[-1])

    return apply


def init(key, shapes, signed_constant: bool):
    keys = jax.random.split(key, len(shapes))
    out = []
    for k, (s, fan_in) in zip(keys, shapes):
        std = EMBED_STD if fan_in is None else math.sqrt(2.0 / fan_in)
        n = jax.random.normal(k, s)
        out.append(jnp.sign(n) * std if signed_constant else n * std)
    return out
