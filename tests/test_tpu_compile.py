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
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import mrc
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


# The PR cell's fixed-block encode: 10 clients, d = 198,800 in 777 blocks
# of 256, n_is 256, the registry's chunk of 16.
ENC_CLIENTS, ENC_BLOCKS, ENC_SIZE, ENC_NIS = 10, 777, 256, 256


def _loop_body_outputs(hlo: str):
    """(computation, output type) of every top-level instruction of each
    while body in optimised HLO text; fused computations are not bodies."""
    bodies = set(re.findall(r"body=%([\w.\-]+)", hlo))
    comp = None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%(\S+) .*\{$", line)
        if head:
            comp = head.group(1)
        elif line == "}":
            comp = None
        elif comp in bodies and " = " in line:
            rhs = line.split(" = ", 1)[1]
            if rhs.startswith("("):  # a tuple type: up to its closing paren
                depth = 0
                for i, ch in enumerate(rhs):
                    depth += (ch == "(") - (ch == ")")
                    if depth == 0:
                        break
                yield comp, rhs[:i + 1]
            else:
                yield comp, rhs.split(" ", 1)[0]


def test_fixed_encode_writes_no_candidate_tensor(one_chip):
    """No top-level op of the chunk loop outputs a (clients, chunk, n_is, S)
    buffer: the candidates are generated once, inside the scoring fusion,
    and the sample is regenerated from the selected rows alone."""
    def enc(sk, sel, q, p):
        return jax.vmap(lambda k, s, q, p: mrc.encode_fixed(
            k, s, q, p, n_is=ENC_NIS, chunk=CHUNK))(sk, sel, q, p)

    keys = _shape(one_chip, (ENC_CLIENTS, 2), jnp.uint32)
    blocks = _shape(one_chip, (ENC_CLIENTS, ENC_BLOCKS, ENC_SIZE))
    hlo = jax.jit(enc).lower(keys, keys, blocks, blocks).compile().as_text()
    outputs = list(_loop_body_outputs(hlo))
    assert outputs, "the chunk loop's body was not found"
    full = f"[{ENC_CLIENTS},{CHUNK},{ENC_NIS},{ENC_SIZE}]"
    assert not [o for o in outputs if full in o[1]]


# The 2NN's local training, vmapped over the cells' 10 clients.  1,280
# samples a client is about the smallest shard at which the image fetch
# still made XLA carry a sample-minor copy of the shards into the step loop
# (1,248 did not); the cells hold 6,000.
FETCH_CLIENTS, FETCH_SHARD, FETCH_HW = 10, 1280, 28


def _shard_layouts(hlo: str):
    """Minor-to-major layouts of the (clients, shard, ...) buffers that a
    while loop carries: the shard copies the step loop reads."""
    carried = " ".join(line.split(" = ", 1)[1].split(" while(")[0]
                       for line in hlo.splitlines() if " while(" in line)
    dims = rf"\w+\[{FETCH_CLIENTS},{FETCH_SHARD},[\d,]+\]"
    return [tuple(int(i) for i in m.split(","))
            for m in re.findall(dims + r"\{([\d,]+)", carried)]


@pytest.mark.parametrize("kind", ["mask", "dense"])
def test_mlp_fetch_gathers_rows(one_chip, kind):
    """Each step of a ``make_mlp`` net's local training gathers whole rows
    of 784 features, from a copy of the shards laid out sample-major; a
    sample-minor copy made each image a gather of one lane per tile."""
    from repro.fl.nets import make_mlp
    from repro.fl.tasks import make_cfl_task, make_mask_task

    in_dim = FETCH_HW * FETCH_HW
    net = make_mlp(in_dim=in_dim, widths=(200, 200),
                   signed_constant=kind == "mask")
    key = jax.random.PRNGKey(0)
    x_test = jnp.zeros((8, FETCH_HW, FETCH_HW, 1))
    y_test = jnp.zeros((8,), jnp.int32)
    if kind == "mask":
        task = make_mask_task(net, key, x_test, y_test)
    else:
        task, _ = make_cfl_task(net, key, x_test, y_test)
    n, s = FETCH_CLIENTS, FETCH_SHARD
    hlo = jax.jit(lambda t, *a: jax.vmap(t.local_train)(*a)).lower(
        task, _shape(one_chip, (n, task.d)),
        _shape(one_chip, (n, s, FETCH_HW, FETCH_HW, 1)),
        _shape(one_chip, (n, s), jnp.int32),
        _shape(one_chip, (n, 2), jnp.uint32)).compile().as_text()
    fetch = [line for line in hlo.splitlines()
             if " gather(" in line and "local.batch" in line]
    slices = {re.search(r"slice_sizes=\{([\d,]+)\}", line).group(1)
              for line in fetch}
    assert slices == {"1,1", f"1,1,{in_dim}"}  # labels, rows
    layouts = _shard_layouts(hlo)
    assert layouts, "no shard copy is carried by the step loop"
    assert all(lay[0] != 1 for lay in layouts), layouts
