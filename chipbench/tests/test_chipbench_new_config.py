"""A configuration comes in as new files only. In a copy of the benchmark,
a second configuration is added without touching a file the copy had: a
kind for a dense texture map (5 x 5 windows at stride 1, offset (1,1),
8 levels over the fixed range 0..255), its configuration, its cell and
one reader of a program span, with their entries appended to
``BENCHMARK.json``. The copy's own harness then serves that cell through
``GLCMEngine`` at test size on the CPU and finds it correct, and every
file the copy had is byte for byte as it was."""

import contextlib
import importlib
import importlib.util
import json
import shutil
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src")]
NEW = Path(__file__).parent / "fixtures" / "texture_config"
CELL = "texture_fixture.map_closed"
READER = "dispatch_ms.texture_fixture"


def files(root: Path) -> dict:
    return {str(p.relative_to(root)): p.read_bytes() for p in sorted(root.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


@contextlib.contextmanager
def chipbench_from(root: Path):
    """Import ``chipbench`` from ``root`` for the duration, then put the
    modules and the path back as they were."""
    def ours(name):
        return name == "chipbench" or name.startswith("chipbench.")

    saved = {k: v for k, v in sys.modules.items() if ours(k)}
    path = list(sys.path)
    for k in saved:
        del sys.modules[k]
    sys.path.insert(0, str(root))
    try:
        yield importlib.import_module("chipbench.run")
    finally:
        for k in [k for k in sys.modules if ours(k)]:
            del sys.modules[k]
        sys.modules.update(saved)
        sys.path[:] = path


@pytest.fixture
def copy(tmp_path):
    """The benchmark copied, and the new configuration added as files and
    entries: (the copy's root, the files it had before the addition)."""
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "chipbench", root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    had = files(root)
    for src in NEW.rglob("*"):
        if src.is_file() and src.name != "benchmark_entries.json":
            dst = root / "chipbench" / src.relative_to(NEW)
            assert not dst.exists(), f"{dst} is not a new file"
            shutil.copy(src, dst)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for key, entries in json.loads((NEW / "benchmark_entries.json").read_text()).items():
        bench[key] += entries
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1) + "\n")
    return root, had


@pytest.mark.parametrize("traced", [False, True], ids=["trace0", "trace1"])
def test_new_configuration_runs_from_new_files_only(copy, monkeypatch, traced):
    root, had = copy
    assert had == {k: v for k, v in files(ROOT).items()
                   if k == "BENCHMARK.json" or k.startswith("chipbench/")}
    with chipbench_from(root) as run:
        assert Path(run.__file__).is_relative_to(root)
        spec = importlib.util.spec_from_file_location(
            "copy_cpu_cells", root / "chipbench" / "tests" / "cpu_cells.py")
        cpu_cells = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(cpu_cells)
        assert CELL in cpu_cells.CELLS
        cpu_cells.shrink(monkeypatch)
        if traced:
            # a CPU capture has no device plane: stand in one that never works
            trace = importlib.import_module("chipbench.trace")
            real = trace.load
            monkeypatch.setattr(trace, "load", lambda path: dict(
                real(path), devices=[{"name": "cpu", "ops": []}]))
        result = run.run_cell(CELL, 2**40 + 41, 0.5, traced=traced)
    assert result["correct"] and result["failed"] == 0 and result["attempted"] > 0
    check = result["checks"]["feature_err"]
    assert check["value"] <= check["limit"] == 1e-4
    if traced:
        assert set(result["metrics"]) == {READER}
        assert result["metrics"][READER]["value"] > 0
        assert result["breakdown"]["idle_gaps"][0][0].startswith("glcm.")
    else:
        assert set(result["metrics"]) == {"throughput_mvox_s", "setup_s"}
    now = files(root)
    old_bench, new_bench = json.loads(had["BENCHMARK.json"]), json.loads(now["BENCHMARK.json"])
    for key, value in old_bench.items():
        assert new_bench[key] == value or new_bench[key][:len(value)] == value
    assert {k: now[k] for k in had if k != "BENCHMARK.json"} == \
        {k: v for k, v in had.items() if k != "BENCHMARK.json"}


def test_texture_kind_finds_a_left_out_window(copy):
    """The texture kind's comparison sees one window's answer altered."""
    root, _ = copy
    with chipbench_from(root):
        kinds = importlib.import_module("chipbench.kinds")
        run = importlib.import_module("chipbench.run")
        _, _, cell, config = run.load_cell(CELL)
        kind = kinds.of(config)
        (img,) = kind.make_pool([{"kind": "iid_u8", "count": 1}], cell["shape"], 7)
        want = kind.reference(img, config)
        engine = kind.build_engine(cell, config)
        got = np.array(engine.result(engine.submit(img)))
        assert got.shape == want.shape == (20, 16, 1, 5)
        assert kind.error(got, want) <= config["feature_err_limit"]
        got[3, 4] = got[3, 5]
        assert kind.error(got, want) > 100 * config["feature_err_limit"]
        assert kind.worst(got, want).startswith("window (3, 4), offset 0")
    assert jax.devices()[0].platform == "cpu"
