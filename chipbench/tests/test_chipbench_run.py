"""chipbench/run.py end to end without the chip: it refuses to run on the
CPU, and with the chip check lifted it drives each cell at a small size and
finds the answers correct."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src"), str(Path(__file__).parent)]

from chipbench import run  # noqa: E402
from cpu_cells import SMALL, shrink  # noqa: E402


def test_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "paper_table3.16k_closed",
         "--seed", str(2**40 + 1), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


@pytest.mark.parametrize("name", sorted(SMALL))
def test_cell_runs_correct_at_small_size(monkeypatch, name):
    shrink(monkeypatch)
    result = run.run_cell(name, 2**40 + 17, 0.5, traced=False)
    assert result["correct"] and result["failed"] == 0 and result["attempted"] > 0
    assert list(result)[-1] == "checks"
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    want = {m["name"] for m in bench["end_to_end"] if run.reports(m, name)}
    assert set(result["metrics"]) == want
    for m in result["metrics"].values():
        assert m["value"] > 0
    assert result["device"]["count"] >= 1
