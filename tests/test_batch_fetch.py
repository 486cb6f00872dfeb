"""Local training fetches each step's batch in the form the net's first
layer reads (``Net.reads_rows``, ``fl/tasks.py`` ``_fetch_form``), and the
form changes no number; the 2NN's first layer sums its inputs in a fixed
order (``nets.ordered_dot``).

A net whose ``apply`` flattens each sample (``make_mlp``) trains on rows of
a ``(shard, features)`` copy of the shard; nets that read images keep their
images.  Either form feeds ``apply`` the same values in the same order, so
each task's ``local_train`` returns bit for bit what the image fetch
(``xs[idx]`` on the image-shaped shard) returns, for the same key.
"""
import jax
import numpy as np
import pytest

from repro.fl import nets
from repro.fl.tasks import make_cfl_task, make_mask_task

SHARD, HW = 48, 8
NETS = {
    "mlp": lambda signed: nets.make_mlp(in_dim=HW * HW * 3, widths=(24, 16),
                                        signed_constant=signed),
    "vit": lambda signed: nets.make_vit(hw=HW, channels=3, patch=4, width=16,
                                        depth=1, heads=2, mlp_width=32,
                                        signed_constant=signed),
}


def _task(kind, net, key, x_test, y_test):
    kw = dict(local_epochs=2, batch_size=16)
    if kind == "mask":
        return make_mask_task(net, key, x_test, y_test, **kw), None
    return make_cfl_task(net, key, x_test, y_test, local_lr=3e-3, **kw)


@pytest.mark.parametrize("name", sorted(NETS))
@pytest.mark.parametrize("kind", ["mask", "dense"])
def test_local_train_same_under_either_fetch(kind, name):
    """The task as its net builds it against the same task with the other
    fetch form; for the MLP the other form is the image fetch."""
    key = jax.random.PRNGKey(11)
    xs = jax.random.normal(jax.random.fold_in(key, 1), (SHARD, HW, HW, 3))
    ys = jax.random.randint(jax.random.fold_in(key, 2), (SHARD,), 0, 10)
    net = NETS[name](kind == "mask")
    task, theta0 = _task(kind, net, jax.random.fold_in(key, 3), xs[:8], ys[:8])
    other, _ = _task(kind, net._replace(reads_rows=not net.reads_rows),
                     jax.random.fold_in(key, 3), xs[:8], ys[:8])
    theta = task.init_theta() if kind == "mask" else theta0
    k = jax.random.fold_in(key, 4)
    got = np.asarray(task.local_train(theta, xs, ys, k))
    want = np.asarray(other.local_train(theta, xs, ys, k))
    assert np.any(got != np.asarray(theta if kind == "mask" else 0.0))
    np.testing.assert_array_equal(got, want)


def test_reads_rows_set_by_make_mlp_alone():
    assert nets.make_mlp(in_dim=12).reads_rows
    assert not nets.make_cnn(hw=8).reads_rows
    assert not NETS["vit"](False).reads_rows
    assert not nets.Net(init=None, apply=None).reads_rows


def test_ordered_dot_sums_pieces_in_order():
    """784 inputs: halves of 392, each in pieces of 128 from its start."""
    kx, kw = jax.random.split(jax.random.PRNGKey(2))
    x = jax.random.normal(kx, (16, 784))
    w = jax.random.normal(kw, (784, 24))
    cuts = (0, 128, 256, 384, 392, 520, 648, 776, 784)

    def by_hand(x, w):
        out = x[:, :128] @ w[:128]
        for a, b in zip(cuts[1:-1], cuts[2:]):
            out = out + x[:, a:b] @ w[a:b]
        return out

    got = np.asarray(jax.jit(nets.ordered_dot)(x, w))
    np.testing.assert_array_equal(got, np.asarray(jax.jit(by_hand)(x, w)))
    np.testing.assert_allclose(got, np.asarray(x @ w), rtol=1e-4, atol=1e-4)
