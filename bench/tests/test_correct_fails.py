"""``correct`` comes out false under the control and under each fault a
cell can have, with the timed path broken underneath the harness."""
import jax.numpy as jnp
import pytest

from conftest import CELLS

import run as bench_run
from harness import faults


def _run(cell, seed, **hooks):
    args = bench_run.parse(["--workload", cell, "--seed", str(seed),
                            "--seconds", "0.2", "--trace", "0",
                            "--rehearse"])
    result, rows = bench_run.run_cell(args, **hooks)
    return result


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("seed", [11, 2 ** 31 + 12, 4000000013])
def test_control_bfloat16_fails(cell, seed):
    res = _run(cell, seed,
               call_hook=faults.reference_in_place(jnp.bfloat16))
    assert res["correct"] is False, res["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_state_unchanged_fails(cell):
    res = _run(cell, 21, call_hook=faults.unchanged)
    assert res["correct"] is False, res["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_half_cohort_fails(cell):
    res = _run(cell, 22, spec_transform=faults.half_cohort)
    assert res["correct"] is False, res["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    assert _run(cell, 23)["correct"] is True
