"""The harness end to end on the CPU, and its refusals."""
import json
import shutil
import subprocess
import sys

import pytest

from conftest import BENCH, CELLS

import run as bench_run


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_runs_correct(cell, trace, capsys):
    rc = bench_run.main(["--workload", cell, "--seed", str(2 ** 31 + 7),
                         "--seconds", "0.5", "--trace", str(trace),
                         "--rehearse"])
    assert rc == 0
    lines = capsys.readouterr().out.strip().splitlines()
    res = json.loads(lines[-1])
    assert res["correct"] is True
    assert res["metrics"] == {} and res["rehearsal"] is True
    assert list(res)[-1] == "checks"
    assert any("compiles_in_window=0" in x for x in lines)


def test_cpu_without_rehearsal_refused(capsys):
    rc = bench_run.main(["--workload", CELLS[0], "--seed", "1",
                         "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert not capsys.readouterr().out.strip().endswith("}")


def test_bench_alone_refused(tmp_path):
    """A directory with only BENCHMARK.json and bench/ has no program."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(BENCH.parent / "BENCHMARK.json", tmp_path)
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        CELLS[0], "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, capture_output=True,
                       text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
