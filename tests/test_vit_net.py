"""``nets.make_vit`` against the benchmark's plain reference of the ViT
(``bench/reference/nets/make_vit.py``), on the CPU at a small size.

The reference is a separate plain ``jax.numpy`` float32 implementation of
the same equations; both run at HIGHEST precision here, so what is left
between them is the order of float32 operations (LayerNorm by rsqrt or by
a divide, GELU's two forms, the head split of attention): a few ulps of
the logits, hence the tolerances below."""
import importlib.util
import math
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.fl import nets
from repro.fl.tasks import make_mask_task

REF_PATH = (Path(__file__).resolve().parents[1] / "bench" / "reference"
            / "nets" / "make_vit.py")
ARGS = dict(hw=16, channels=3, patch=4, width=32, depth=2, heads=4,
            mlp_width=64, n_classes=10)
RTOL, ATOL = 2e-5, 2e-5  # float32 at HIGHEST: reordered sums of ~100 terms


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location("bench_ref_make_vit",
                                                  REF_PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def setup():
    key = jax.random.PRNGKey(7)
    net = nets.make_vit(**ARGS, signed_constant=True)
    x = jax.random.normal(jax.random.fold_in(key, 1), (6, 16, 16, 3))
    y = jax.random.randint(jax.random.fold_in(key, 2), (6,), 0, 10)
    return key, net, x, y


def test_shapes_and_d_match_reference(ref, setup):
    key, net, _, _ = setup
    shapes = ref.layer_shapes(**ARGS)
    got = [tuple(w.shape) for w in net.init(key)]
    assert got == [s for s, _ in shapes]
    flat, _ = nets.flatten_weights(net.init(key))
    assert flat.shape[0] == sum(math.prod(s) for s, _ in shapes) == 18816


@pytest.mark.parametrize("signed", [True, False])
def test_init_bit_for_bit(ref, signed):
    key = jax.random.PRNGKey(11)
    w = nets.make_vit(**ARGS, signed_constant=signed).init(key)
    r = ref.init(key, ref.layer_shapes(**ARGS), signed)
    assert len(w) == len(r)
    for a, b in zip(w, r):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_published_width_d():
    """ViT-B/16 at 4 of its 12 layers is exactly 113,520 blocks of 256."""
    shapes = jax.eval_shape(nets.make_vit(
        hw=224, channels=3, patch=16, width=768, depth=4, heads=12,
        mlp_width=3072, n_classes=10).init, jax.random.PRNGKey(0))
    d = sum(math.prod(s.shape) for s in shapes)
    assert d == 29_061_120 == 113_520 * 256


def test_logits_match_reference(ref, setup):
    key, net, x, _ = setup
    w = net.init(key)
    with jax.default_matmul_precision("highest"):
        got = net.apply(w, x)
    want = ref.make_apply(**ARGS)(w, x)
    assert got.shape == (6, 10)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=RTOL, atol=ATOL)


def test_ste_gradient_matches_reference(ref, setup):
    """The gradient in the scores of ``MaskTask``'s straight-through loss,
    against the same loss written on the reference's forward pass."""
    key, net, x, y = setup
    task = make_mask_task(net, key, x, y)
    s = 0.5 * jax.random.normal(jax.random.fold_in(key, 3), (task.d,))
    mk = jax.random.fold_in(key, 4)
    apply = ref.make_apply(**ARGS)
    shapes = [sh for sh, _ in ref.layer_shapes(**ARGS)]

    def ref_loss(s):
        prob = jax.nn.sigmoid(s)
        m = jax.random.bernoulli(mk, prob).astype(jnp.float32)
        m_ste = m + prob - jax.lax.stop_gradient(prob)
        flat = task.w0_flat * m_ste
        ws, off = [], 0
        for sh in shapes:
            ws.append(flat[off:off + math.prod(sh)].reshape(sh))
            off += math.prod(sh)
        logp = jax.nn.log_softmax(apply(ws, x))
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))

    with jax.default_matmul_precision("highest"):
        got = jax.grad(task.ste_loss)(s, x, y, mk)
    want = jax.grad(ref_loss)(s)
    assert float(jnp.max(jnp.abs(want))) > 0
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-6)


def test_vit_scopes_in_compiled_gradient(setup):
    """Forward and transpose of each part carry its ``vit.*`` scope, which
    the benchmark's ``vit_attn_ms`` and ``vit_mlp_ms`` read."""
    key, net, x, y = setup
    task = make_mask_task(net, key, x, y)
    s = jnp.zeros((task.d,))
    text = jax.jit(jax.grad(task.ste_loss)).lower(
        s, x, y, key).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("vit.patch", "vit.attn", "vit.mlp", "vit.head"):
        assert any(f"jvp({scope})" in n for n in names), scope
    for scope in ("vit.attn", "vit.mlp"):
        assert any(f"transpose(jvp({scope}))" in n for n in names), scope


def test_rejects_indivisible_sizes():
    with pytest.raises(ValueError):
        nets.make_vit(**dict(ARGS, patch=5))
    with pytest.raises(ValueError):
        nets.make_vit(**dict(ARGS, heads=5))
