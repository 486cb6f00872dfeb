"""The FL Pallas kernels compile for a TPU v5e chip that is described, not
attached.

Each test lowers a ``repro.kernels.ops`` entry point with
``interpret=False`` at the engine's real shapes and compiles it for one
chip of a described ``v5e:2x2`` topology, so what the chip's compiler
refuses (block tilings it cannot lay out, more VMEM than a kernel may
use) fails here at no chip time.  The widths are the reference MLP
(d = 28,160) and the widest CNN ``fl/nets.py`` builds (d = 421,408).

The topology is described inside a module fixture -- never at import --
and the fixture skips where it cannot be described.  The persistent
compilation cache is off around these compiles: an entry written for a
described chip cannot be read back without one.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.blocks import AdaptiveAllocation
from repro.kernels import ops

WIDTHS = (28160, 421408)
N_CLIENTS = 10
N_IS = 64
CHUNK, BLOCK = 16, 128  # the registry's encoder chunk and the fixed block


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _shape(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel_compiles(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("d", WIDTHS)
@pytest.mark.parametrize("stat", ["total", "profile"])
def test_bernoulli_kl_compiles(one_chip, stat, d):
    fn = {"total": ops.bernoulli_kl_total,
          "profile": ops.bernoulli_kl_profile}[stat]
    x = _shape(one_chip, (N_CLIENTS, d))
    _assert_kernel_compiles(lambda q, p: fn(q, p, interpret=False), x, x)


def test_mrc_logw_compiles(one_chip):
    x = _shape(one_chip, (CHUNK, N_IS, BLOCK))
    ab = _shape(one_chip, (CHUNK, BLOCK))
    _assert_kernel_compiles(
        lambda x, a, b: ops.mrc_logw(x, a, b, interpret=False), x, ab, ab)


@pytest.mark.parametrize("d", WIDTHS)
def test_segment_logw_compiles_at_largest_bucket(one_chip, d):
    n_seg = AdaptiveAllocation(n_is=N_IS).bucket_grid(d)[-1]
    vec = _shape(one_chip, (d,))
    _assert_kernel_compiles(
        lambda u, p, a, b, seg: ops.segment_logw(
            u, p, a, b, seg, n_seg=n_seg, interpret=False),
        _shape(one_chip, (N_IS, d)), vec, vec, vec,
        _shape(one_chip, (d,), jnp.int32))
