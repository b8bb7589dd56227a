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
from cpu_cells import CELLS, shrink  # noqa: E402


def test_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "paper_table3.16k_closed",
         "--seed", str(2**40 + 1), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


@pytest.mark.parametrize("name", CELLS)
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


@pytest.mark.parametrize("name", CELLS)
def test_traced_cell_reads_program_spans_at_small_size(monkeypatch, name):
    """A traced run hands the readers the program's spans and the engine's
    counters of the window, and puts the idle time down to the program's
    spans in the result line's breakdown. A CPU capture has no device
    plane: a stand-in one that never works leaves the whole window idle."""
    from chipbench import trace

    shrink(monkeypatch)
    real = trace.load
    monkeypatch.setattr(trace, "load", lambda path: dict(
        real(path), devices=[{"name": "cpu", "ops": []}]))
    seen = {}
    real_reader = run.reader

    def spy(metric):
        read = real_reader(metric)

        def wrapped(ctx):
            seen["ctx"] = ctx
            return read(ctx)
        return wrapped

    monkeypatch.setattr(run, "reader", spy)
    result = run.run_cell(name, 2**40 + 19, 0.5, traced=True)
    assert result["correct"] and list(result)[-1] == "checks"
    ctx = seen["ctx"]
    assert ctx.served == len(ctx.records) == result["attempted"]
    assert ctx.stats["served"] == ctx.served and ctx.stats["batches"] > 0
    assert ctx.span_ns["glcm.dispatch"] > ctx.span_ns["glcm.h2d"] > 0
    gaps = result["breakdown"]["idle_gaps"]
    assert gaps[0][0].startswith("glcm.") and len(gaps) <= 10
    assert {k for k, _ in gaps} >= {"glcm.h2d", "glcm.launch"}
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    spans = [m["name"] for m in bench["per_layer"]
             if m["source"] == "program_span" and run.reports(m, name)]
    assert spans and set(spans) <= set(result["metrics"])
    for metric in spans:
        assert result["metrics"][metric]["value"] > 0
