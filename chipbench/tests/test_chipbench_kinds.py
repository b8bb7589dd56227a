"""Configuration kinds: the ``image`` kind reads as the harness read the
whole-image configuration before kinds existed, the backend check looks at
the plans the engine serves, and the engine's counters are taken over the
window."""

import hashlib
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import kinds, run  # noqa: E402

# Read from the harness as it stood before kinds (data.make_pool,
# run.reference_answers, reference.feature_error of the answers that
# run.build_engine's engine serves, roofline.work as run.py called it) at
# the CPU size 48 x 40 of paper_table3.16k_closed: the first 16 hex digits
# of the sha256 of each pool entry's bytes and of each float64 reference
# answer's bytes, the feature_err of each entry, and the work.
PARENT = {
    2**40 + 31: {
        "pool": ["4a51a7a5ebea3232", "cb0dc45ff381efa9", "fc444502083b630f",
                 "c526a63b8efc2072"],
        "want": ["4cfd56e45dfa158c", "904a828499b4638c", "9bb5bd1bf7c32fd5",
                 "fe9b2437ef011c99"],
        "feature_err": [2.7052572152950793e-06, 1.066433023503332e-06,
                        1.1786521686035507e-05, 1.7042092972027887e-05],
    },
    3914000777: {
        "pool": ["d4ae75040cf550d1", "610f8de3126564d9", "f7e8484b86f24d6e",
                 "329e17b72ccc41a2"],
        "want": ["cd67a6a2e2900f5b", "21c0eea9d03c0f17", "5c3c40242caf8c7b",
                 "ec8f737fd14e4ba2"],
        "feature_err": [1.6204211064529209e-06, 1.6991695582725632e-06,
                        1.4682168685370239e-05, 9.533154568181395e-06],
    },
}
PARENT_WORK = (14370816, 18304)
CELL = "paper_table3.16k_closed"


def sha(a) -> str:
    return hashlib.sha256(np.ascontiguousarray(a).tobytes()).hexdigest()[:16]


@pytest.fixture(scope="module")
def image_cell():
    _, _, cell, config = run.load_cell(CELL)
    kind = kinds.of(config)
    small, _ = kind.cpu_cell(cell, config)
    return kind, small, config, kind.build_engine(small, config)


@pytest.mark.parametrize("seed", sorted(PARENT))
def test_image_kind_reads_as_before(image_cell, seed):
    kind, cell, config, engine = image_cell
    assert kind.__name__ == "chipbench.kinds.image" and cell["shape"] == [48, 40]
    pool = kind.make_pool(cell["pool"], cell["shape"], seed)
    assert [sha(p) for p in pool] == PARENT[seed]["pool"]
    want = run.reference_answers(kind, pool, range(len(pool)), config)
    assert [sha(np.asarray(want[i], np.float64)) for i in range(len(pool))] == \
        PARENT[seed]["want"]
    got = [engine.result(engine.submit(p)) for p in pool]
    errors = [kind.error(g, want[i]) for i, g in enumerate(got)]
    assert errors == pytest.approx(PARENT[seed]["feature_err"], rel=1e-6)
    assert kind.work(cell, config, pool) == PARENT_WORK


def test_image_kind_worst_names_offset_and_feature():
    _, _, _, config = run.load_cell(CELL)
    want = np.ones((4, 14))
    got = want.copy()
    got[2, 9] = 1.5
    assert kinds.of(config).worst(got, want) == "offset 2, difference_variance"


def test_spec_takes_every_field_of_the_configuration():
    spec = kinds.glcm_spec({"spec": {
        "levels": 8, "pairs": [[1, 135]], "ndim": 2, "quantize": "uniform",
        "vrange": [0, 255], "symmetric": True, "normalize": True, "region": "window",
        "region_shape": [5, 5], "region_stride": [1, 1]}})
    assert spec.region == "window" and spec.region_shape == (5, 5)
    assert spec.region_stride == (1, 1) and spec.vrange == (0.0, 255.0)
    assert spec.pairs == ((1, 135),) and spec.symmetric and spec.normalize
    # paper_table3 as the harness built it by hand before kinds
    from repro.core.spec import GLCMSpec

    _, _, _, config = run.load_cell(CELL)
    assert kinds.glcm_spec(config) == GLCMSpec(
        levels=32, pairs=((1, 0), (1, 45), (4, 0), (4, 45)), ndim=2, quantize="uniform",
        symmetric=False, normalize=False)


def test_backend_check_reads_the_served_plans(image_cell):
    """The plans check_backend inspects are the engine's own (the same
    objects from the plan cache), with the engine's feature set."""
    from repro.core.plan import compile_plan

    _, cell, config, engine = image_cell
    assert run.check_backend(engine, cell, dict(config, expect_backend="onehot")) == "onehot"
    for b in cell["buckets"]:
        served = engine._plan_for(engine._workload(0), b)
        assert compile_plan(engine.spec, (b, *engine.cfg.image_shape),
                            features=engine.cfg.features) is served
    with pytest.raises(run.HarnessError, match="expected 'pallas_fused'"):
        run.check_backend(engine, cell, config)


def test_window_stats_counts_deltas():
    before = {"name": "default", "ndim": 2, "batch_size": 1, "queue_depth": 3,
              "served": 10, "batches": 10, "unstacked_batches": 9, "paused": False,
              "buckets": (1,), "pad_ms": {"p50": 1.0, "mean": 2.0, "n": 4}}
    after = dict(before, queue_depth=1, served=25, batches=20, unstacked_batches=19,
                 pad_ms={"p50": 3.0, "mean": 3.0, "n": 10})
    got = run.window_stats(before, after)
    assert got == {"name": "default", "ndim": 2, "batch_size": 1, "queue_depth": 1,
                   "served": 15, "batches": 10, "unstacked_batches": 10,
                   "paused": False, "buckets": (1,),
                   "pad_ms": {"n": 6, "total": 3.0 * 10 - 2.0 * 4}}
