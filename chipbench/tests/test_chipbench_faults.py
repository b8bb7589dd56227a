"""``correct`` comes out false when the timed path is broken underneath,
once for each fault a cell can have, and the planted faults of
``chipbench.control`` (the bfloat16 control, a block of each input left
uncounted) fail the limit that the program passes."""

import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src"), str(Path(__file__).parent)]

from chipbench import control, data, reference, run  # noqa: E402
from cpu_cells import CELLS, shrink  # noqa: E402


def stale(out, memo, x, config):
    """The engine hands back the previous launch's answers."""
    prev = memo.get(out.shape)
    memo[out.shape] = out.copy()
    return out if prev is None else prev


def altered(out, memo, x, config):
    """One answer altered where it is produced: the first request's first
    offset answers with its second offset's features."""
    out[0, 0] = out[0, 1]
    return out


def uncounted(out, memo, x, config):
    """The last eighth of every row left uncounted: each answer is the
    reference's from such counts, put in the program's place."""
    for i, img in enumerate(np.asarray(x)):
        out[i] = reference.answer(control.uncounted_counts(img, config), config)
    return out


def broken_engine(monkeypatch, fault):
    real = run.build_engine

    def build(cell, config):
        engine = real(cell, config)
        plan_for, memo = engine._plan_for, {}

        def faulty(w, bucket):
            plan = plan_for(w, bucket)
            return lambda x: fault(np.array(plan(x)), memo, x, config)

        engine._plan_for = faulty
        return engine

    monkeypatch.setattr(run, "build_engine", build)


# The cells of whole-image configurations, whose reference and planted
# faults these are. Batch-1 cells: no batch to leave half of, no exchange
# between chips.
IMAGE_CELLS = [c for c in CELLS if run.load_cell(c)[3]["kind"] == "image"]
FAULTS = [(name, fault) for name in IMAGE_CELLS for fault in (stale, altered, uncounted)]


@pytest.mark.parametrize("name,fault", FAULTS, ids=lambda x: getattr(x, "__name__", x))
def test_fault_makes_the_run_incorrect(monkeypatch, name, fault):
    shrink(monkeypatch)
    broken_engine(monkeypatch, fault)
    result = run.run_cell(name, 2**40 + 23, 0.5, traced=False)
    assert not result["correct"] and result["failed"] > 0
    check = result["checks"]["feature_err"]
    assert check["value"] > check["limit"]


def planted_fault_errors(monkeypatch, name, fault):
    shrink(monkeypatch)
    _, _, cell, config = run.load_cell(name)
    for seed in (5, 2**40 + 6, 7):
        pool = data.make_pool(cell["pool"], cell["shape"], seed)
        _, errors = control.fault_errors(pool, range(len(pool)), config)
        yield max(errors[fault]), config["feature_err_limit"]


@pytest.mark.parametrize("name", IMAGE_CELLS)
def test_bfloat16_control_fails_the_limit(monkeypatch, name):
    for err, limit in planted_fault_errors(monkeypatch, name, "control"):
        assert err > limit


@pytest.mark.parametrize("name", IMAGE_CELLS)
def test_uncounted_block_fails_the_limit(monkeypatch, name):
    for err, limit in planted_fault_errors(monkeypatch, name, "uncounted"):
        assert err > limit
