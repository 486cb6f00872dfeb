"""CPU rehearsal of ``chip_smoke.py``.

Every phase of the chip smoke test runs here at a tiny size, with the
Pallas kernels in interpret mode, so the script cannot rot between chip
runs; and ``main()`` must refuse a CPU backend without printing the
``ok`` line.
"""
import importlib.util
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod  # dataclasses resolve through it
    spec.loader.exec_module(mod)
    yield mod
    del sys.modules["chip_smoke"]


def test_phases_run_at_tiny_size(smoke, capsys):
    tiny = smoke.Sizes(n_clients=3, hw=6, per_client=40, n_test=60,
                       width=16, n_is=8, block=32, rounds=2, eval_every=1,
                       local_epochs=1, batch_size=20, wide_hw=8,
                       wide_conv=(4, 8), wide_dense=(16,), wide_rounds=1,
                       expect_d=(736, 996))
    smoke.run_phases(tiny, floors={})
    out = capsys.readouterr().out
    for line in ("[reference] bicompfl-gr:", "[reference] bicompfl-pr:",
                 "[reference] bicompfl-gr-adaptive-avg:",
                 "[reference] bicompfl-gr-adaptive:", "[reference] fedavg:",
                 "[oracle] bicompfl-gr host vs fused: meters_equal=True "
                 "theta_bitwise=True",
                 "[oracle] bicompfl-gr wire audit (2 rounds): reconciled",
                 "[kernels] bicompfl-gr+pallas_logw:",
                 "[kernels] bicompfl-gr-adaptive+segment_logw:",
                 "[kernels] mrc_logw shape=(16, 8): tpu_custom_call=False",
                 "[kernels] segment_logw(n_seg=92) shape=(8, 92)",
                 "[kernels] bernoulli_kl_profile shape=(736,)",
                 "[kernels] bernoulli_kl_total shape=()",
                 "[wide] bicompfl-gr d=996: mode=fused",
                 "[wide] bicompfl-gr-adaptive+segment_logw d=996: "
                 "mode=fused"):
        assert line in out, line
    assert '"ok"' not in out


def test_main_refuses_cpu(smoke, capsys):
    assert smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out
