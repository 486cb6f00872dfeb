"""The FL round names its stages inside the program.

Device side: the fused program's compiled HLO carries each round-stage
scope (``fl.*``, ``FLEngine._build_fused`` / ``_round_core``) and each
sub-scope (``mrc.draw``/``mrc.logw`` in ``core/mrc.py``, ``local.batch`` in
``fl/tasks.py``) that the scheme runs, in its ops' ``op_name`` metadata,
which the TPU profiler reports as each op's name stack.  No scope name
holds a key of the benchmark's older name-stack readers, so those read
what they read before.  Host side: ``FLEngine.run`` writes its ``fl.*``
spans into the profiler's trace.
"""
import importlib.util
import re
from pathlib import Path

import jax
import pytest

from repro.core.blocks import AdaptiveAllocation, FixedAllocation
from repro.fl import registry
from repro.fl.data import make_synthetic, partition_iid
from repro.fl.engine import FLEngine
from repro.fl.faults import FaultPlan
from repro.fl.nets import make_mlp
from repro.fl.tasks import make_cfl_task, make_mask_task

ROOT = Path(__file__).resolve().parents[1]
SCOPE_PREFIXES = ("fl.", "mrc.", "local.")
ROUND = {"fl.train", "fl.uplink", "fl.aggregate", "fl.downlink", "fl.eval"}
MRC = {"mrc.draw", "mrc.logw"}
# scheme -> (task kind, spec factory, run options, scopes its program runs)
CASES = {
    "pr": ("mask", lambda: registry.bicompfl_spec(
        "PR", allocation=FixedAllocation(64), n_is=16, n_dl=3), {},
        ROUND | MRC | {"local.batch"}),
    "fedavg": ("dense", lambda: registry.baseline_spec(
        "fedavg", n=3, d=1472), {}, ROUND | {"local.batch"}),
    "cser-faulted": ("dense", lambda: registry.baseline_spec(
        "cser", n=3, d=1472, reset_period=2),
        {"faults": FaultPlan(seed=1, drop_rate=0.5)},
        ROUND | {"fl.flush", "fl.faults", "local.batch"}),
    "gr-adaptive": ("mask", lambda: registry.bicompfl_spec(
        "GR", allocation=AdaptiveAllocation(n_is=16), n_is=16), {},
        ROUND | MRC | {"fl.control", "local.batch"}),
}


def reader_keys():
    """The name-stack keys of the benchmark's ``local_train_ms`` and
    ``mrc_ms`` readers."""
    keys = []
    for name in ("local_train_ms", "mrc_ms"):
        path = ROOT / "bench" / "metrics" / f"{name}.py"
        spec = importlib.util.spec_from_file_location(f"_reader_{name}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        keys += list(mod.KEYS)
    return keys


@pytest.fixture(scope="module")
def setups():
    k = jax.random.PRNGKey(3)
    train, test = make_synthetic(k, n_train=240, n_test=120, hw=6, noise=0.5)
    shards = partition_iid(jax.random.fold_in(k, 1), train, 3, 80)
    mask = make_mask_task(make_mlp(in_dim=36, widths=(32,),
                                   signed_constant=True),
                          jax.random.fold_in(k, 2), test.x, test.y,
                          local_epochs=1, batch_size=40)
    dense, theta0 = make_cfl_task(make_mlp(in_dim=36, widths=(32,)),
                                  jax.random.fold_in(k, 2), test.x, test.y,
                                  local_epochs=1, batch_size=40,
                                  local_lr=3e-3)
    return {"mask": (mask, None), "dense": (dense, theta0)}, shards


class _Lowered(Exception):
    pass


def fused_hlo(engine, shards, theta0, **run_kw) -> str:
    """The optimized HLO of the engine's fused program for a 2-round run,
    lowered from the run's own arguments (the program is not executed)."""
    build = engine._build_fused

    def spy_build(**kw):
        fn, booked = build(**kw)

        def spy(*args):
            raise _Lowered(fn.lower(*args))
        return spy, booked

    engine._build_fused = spy_build
    with pytest.raises(_Lowered) as caught:
        engine.run(shards, theta0, rounds=2, mode="fused", **run_kw)
    return caught.value.args[0].compile().as_text()


def scopes_of(hlo: str) -> set:
    return {part for name in re.findall(r'op_name="([^"]*)"', hlo)
            for part in name.split("/") if part.startswith(SCOPE_PREFIXES)}


@pytest.mark.parametrize("case", sorted(CASES))
def test_fused_program_carries_stage_scopes(case, setups):
    tasks, shards = setups
    kind, make_spec, run_kw, expected = CASES[case]
    task, theta0 = tasks[kind]
    found = scopes_of(fused_hlo(FLEngine(task, make_spec()), shards, theta0,
                                **run_kw))
    assert found == expected
    keys = reader_keys()
    assert keys and not [(s, k) for s in found for k in keys if k in s]


def test_run_writes_host_spans(setups, tmp_path):
    from jax.profiler import ProfileData
    tasks, shards = setups
    task, _ = tasks["mask"]
    engine = FLEngine(task, CASES["pr"][1]())
    kw = dict(rounds=2, mode="fused", checkpoint_dir=str(tmp_path / "ck"),
              checkpoint_every=1)
    engine.run(shards, None, **kw)  # compile outside the trace
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        engine.run(shards, None, **kw)
    finally:
        jax.profiler.stop_trace()
    (path,) = (tmp_path / "trace").rglob("*.xplane.pb")
    spans = sorted((e.start_ns, e.name)
                   for p in ProfileData.from_file(str(path)).planes
                   for line in p.lines for e in line.events
                   if e.name.startswith("fl."))
    names = [n for _, n in spans]
    # two one-round segments, each dispatched, fetched, booked and saved
    assert names.count("fl.dispatch") == names.count("fl.fetch") == 2
    assert names.count("fl.checkpoint") == 2
    assert names[0] == "fl.prepare" and "fl.book" in names
    for i, n in enumerate(names):
        if n == "fl.dispatch":
            assert names[i + 1:i + 3] == ["fl.fetch", "fl.book"]
