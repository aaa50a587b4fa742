"""A run of each cell at a small size on the CPU, with the chip check
skipped: the whole harness from traffic to the result, correct as it
stands and not correct with a fault planted in the timed path; and a run
that finds no TPU, which must exit with no result.

    python -m pytest benchmarks/chip/tests
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE / "tests"))
sys.path.insert(0, str(HERE))

import faults  # noqa: E402
import smoke  # noqa: E402

CELLS = [w["name"] for w in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]


def rehearse(cell, seed=2718281801, fault=None, control=False):
    import run
    cfg, ov = smoke.overrides(cell)
    return run.run_cell(["--workload", cell, "--seed", str(seed),
                         "--seconds", "8", "--trace", "0"], allow_cpu=True,
                        cfg_override=cfg, cell_override=ov, fault=fault,
                        control=control)


def cpu_env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    return env


def test_no_tpu_no_result():
    p = subprocess.run([sys.executable, str(HERE / "run.py"), "--workload",
                        CELLS[0], "--seed", "4000000001", "--seconds", "2",
                        "--trace", "0"], cwd=ROOT, env=cpu_env(),
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "TPU" in p.stderr


def test_alone_in_a_directory_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable, "benchmarks/chip/run.py", "--workload",
                        CELLS[0], "--seed", "7", "--seconds", "2", "--trace",
                        "0"], cwd=tmp_path, env=cpu_env(),
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout == ""


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_is_correct(cell, capfd):
    import run
    res = rehearse(cell)
    err = capfd.readouterr().err.strip().splitlines()
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] == 8          # 1 req/s for 8 s
    assert res["failed"] == 0
    want = {m["name"] for m in run.load_cell(cell).end_to_end()}
    assert set(res["metrics"]) == want
    assert all(v["value"] > 0 for v in res["metrics"].values())
    # each compared number beside its limit, as the last lines
    assert all(line.startswith("check ") for line in err[-len(res["checks"]):])
    assert list(res["checks"]) == ["max_logit_gap", "tokens_compared",
                                   "overlong", "out_of_vocab",
                                   "unfinished_after_drain"]


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_not_correct(cell, fault):
    res = rehearse(cell, fault=faults.FAULTS[fault])
    assert res["correct"] is False
    gap = res["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_judged_by_the_same_checks(cell, monkeypatch):
    # at this size the int8 control reads as close as the program, so it is
    # moved far off: the verdict must follow the control's number alone
    import run
    real = run.reference_gaps

    def far_off(*a, **k):
        gap, ctl, n = real(*a, **k)
        assert ctl == ctl                     # the control was computed
        return gap, ctl + 100.0, n
    monkeypatch.setattr(run, "reference_gaps", far_off)
    res = rehearse(cell, control=True)
    gap = res["checks"]["max_logit_gap"]
    assert res["correct"] is False
    assert gap["value"] >= 100.0 > gap["limit"] > res["program_gap"]
