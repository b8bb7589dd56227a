"""The ``texture`` configuration's kind (``chipbench/kinds/otb_texture.py``):
its blocked ``np.bincount`` reference against a window-by-window count,
its features against ``chipbench.reference``'s and the cluster formulas,
its work, the engine that holds each answer once, and the cell served on
the CPU: correct as served, incorrect with one window altered or one pair
of every window left uncounted, and the bfloat16 control over the limit."""

import json
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src"), str(Path(__file__).parent)]

from chipbench import kinds, run  # noqa: E402
from chipbench import reference as ref  # noqa: E402
from chipbench.kinds import otb_texture as tex  # noqa: E402
from cpu_cells import shrink  # noqa: E402

CELL = "texture.tiles_closed"
CONFIG = json.loads((ROOT / "chipbench/configs/texture.json").read_text())


def config_with(**spec):
    return dict(CONFIG, spec=dict(CONFIG["spec"], **spec))


def window_loop(raw, config, drop_pair=False):
    """The reference's answer counted one window at a time by
    ``chipbench.reference.counts``."""
    spec = config["spec"]
    levels = spec["levels"]
    q = tex.binned(raw, spec).astype(np.uint8)
    offs = ref.offsets(spec["pairs"], 2)
    rh, rw = spec["region_shape"]
    gh, gw = raw.shape[0] - rh + 1, raw.shape[1] - rw + 1
    out = np.zeros((gh, gw, len(offs), len(tex.FEATURES)))
    for i in range(gh):
        for j in range(gw):
            m = ref.counts(q[i:i + rh, j:j + rw], levels, offs)
            out[i, j] = tex.features(np.moveaxis(m, 0, -1))
    return out


@pytest.mark.parametrize("pairs,levels,shape", [
    ([[1, 135]], 8, (12, 17)),
    ([[1, 45]], 8, (9, 14)),
    ([[1, 0], [1, 90]], 16, (11, 10)),
    ([[2, 135], [1, 45]], 16, (8, 13)),
], ids=["dx+1", "dx-1", "two-offsets-L16", "d2-L16"])
def test_blocked_reference_equals_a_window_loop(monkeypatch, pairs, levels, shape):
    monkeypatch.setattr(tex, "BLOCK_ROWS", 3)
    raw = np.random.default_rng(levels + len(pairs)).integers(0, 256, shape, np.uint8)
    config = config_with(pairs=pairs, levels=levels)
    # the reference takes its centered sums from moments: equal to float64 rounding
    np.testing.assert_allclose(tex.reference(raw, config), window_loop(raw, config),
                               rtol=1e-10, atol=1e-10)


def test_dropped_pair_reference_counts_one_pair_fewer():
    raw = np.random.default_rng(3).integers(0, 256, (10, 9), np.uint8)
    code = tex.pair_codes(tex.binned(raw, CONFIG["spec"]), 8, (1, 1))
    full = tex.window_counts(code, 8, (1, 1), (5, 5), 5, (0, 6))
    short = tex.window_counts(code, 8, (1, 1), (5, 5), 5, (0, 6), drop_pair=True)
    assert (full.sum(axis=(0, 1)) == 16).all()
    assert (short.sum(axis=(0, 1)) == 15).all()
    assert (full >= short).all()


def test_features_agree_with_the_benchmark_reference_and_the_cluster_formulas():
    counts = np.random.default_rng(5).integers(0, 6, (4, 8, 8))
    counts[0] = 0
    counts[0, 3, 3] = 16                     # one level: sigma = 0
    got = tex.features(np.moveaxis(counts, 0, -1))
    p = counts + np.swapaxes(counts, -1, -2)
    p = p / p.sum(axis=(-2, -1), keepdims=True)
    want = ref.features(p)
    for f, name in enumerate(tex.FEATURES[:5]):
        np.testing.assert_allclose(got[:, f], want[:, ref.FEATURE_NAMES.index(name)],
                                   rtol=1e-9, atol=1e-9)
    assert got[0, tex.FEATURES.index("correlation")] == 0.0
    for n, m in enumerate(p):
        mu_x = sum(i * m[i, j] for i in range(8) for j in range(8))
        mu_y = sum(j * m[i, j] for i in range(8) for j in range(8))
        shade = sum((i + j - mu_x - mu_y) ** 3 * m[i, j] for i in range(8) for j in range(8))
        prom = sum((i + j - mu_x - mu_y) ** 4 * m[i, j] for i in range(8) for j in range(8))
        assert got[n, 5] == pytest.approx(shade, rel=1e-12, abs=1e-10)
        assert got[n, 6] == pytest.approx(prom, rel=1e-12, abs=1e-10)


def test_work_of_the_cell():
    cell = json.loads((ROOT / f"chipbench/workloads/{CELL}.json").read_text())
    pool = [np.zeros((1, 1), np.uint8)]
    ops, nbytes = tex.work(cell, CONFIG, pool)
    assert ops == 2 * 64 * 16 * 4092 * 4092 == 34_292_662_272
    assert nbytes == 4096 * 4096 + 4092 * 4092 * 7 * 4 == 485_622_208


def test_held_once_lets_go_of_answers_equal_in_every_bit():
    answers = iter([np.arange(6.0), np.arange(6.0), np.arange(6.0) + 1, np.arange(6.0)])

    class Engine:
        spec = "spec"

        def submit(self, image):
            return id(object())

        def result(self, ticket):
            return next(answers)

    engine = tex.HeldOnce(Engine())
    assert engine.spec == "spec"
    image, other = np.zeros(3), np.zeros(3)
    got = [engine.result(engine.submit(im)) for im in (image, image, image, other)]
    engine._settle.shutdown(wait=True)
    assert got[1].held is got[0].held             # equal bits: the first one held
    assert got[2].held is not got[0].held         # differs: keeps its own
    assert got[3].held is not got[0].held         # another request
    assert [np.asarray(a).tolist() for a in got] == [
        list(range(6)), list(range(6)), list(range(1, 7)), list(range(6))]
    assert tex.error(got[2], np.arange(6.0)) > 0.5
    assert tex.error(got[1], np.arange(6.0)) == 0.0


def test_held_once_waits_while_its_compares_are_behind(monkeypatch):
    real = tex.same_bits
    monkeypatch.setattr(tex, "same_bits", lambda a, b: time.sleep(0.2) or real(a, b))

    class Engine:
        def submit(self, image):
            return id(object())

        def result(self, ticket):
            return np.zeros(4)

        def stats(self):
            return {"workloads": {0: {"served": 3}}}

    engine = tex.HeldOnce(Engine(), workers=1, pending=1)
    image = np.zeros(3)
    got = [engine.result(engine.submit(image)) for _ in range(3)]
    assert 0.1 < engine.wait_s < 1.0          # the third waited on the second's compare
    engine._settle.shutdown(wait=True)
    assert all(a.held is got[0].held for a in got)
    assert engine.stats()["workloads"][0] == {
        "served": 3, "held_once_let_go": 2,
        "held_once_wait_us": int(engine.wait_s * 1e6)}


def test_same_bits_tells_signed_zeros_and_nans_apart():
    a = np.array([0.0, np.nan], np.float32)
    assert tex.same_bits(a, a.copy(), chunk=1)
    planar = np.arange(24, dtype=np.float32).reshape(2, 3, 4).transpose(1, 2, 0)
    assert tex.memory_order(planar) is not None
    copy = planar.copy(order="K")
    assert tex.same_bits(planar, copy, chunk=5) and tex.same_bits(planar, planar.copy())
    copy[1, 2, 1] += 1
    assert not tex.same_bits(planar, copy, chunk=5) and not tex.same_bits(planar, planar + 1)
    assert not tex.same_bits(a, np.array([-0.0, np.nan], np.float32))
    assert not tex.same_bits(a, a.astype(np.float64))


def test_cell_served_on_the_cpu_is_correct(monkeypatch):
    shrink(monkeypatch)
    engines = []
    real = run.build_engine
    monkeypatch.setattr(run, "build_engine",
                        lambda cell, config: engines.append(real(cell, config)) or engines[-1])
    result = run.run_cell(CELL, 2**40 + 23, 0.5, traced=False)
    assert result["correct"] and result["failed"] == 0 and result["attempted"] > 4
    check = result["checks"]["feature_err"]
    assert check["limit"] == CONFIG["feature_err_limit"] and check["value"] <= 1e-6
    (engine,) = engines
    plan = engine._engine._plan_for(engine._engine._workload(0), 1)
    assert plan.spec.scheme == "onehot" and not plan.window_features


def broken(monkeypatch, fault):
    """The cell's engine with ``fault`` applied to every answer the plan
    makes."""
    real = run.build_engine

    def build(cell, config):
        engine = real(cell, config)
        inner = engine._engine
        plan_for = inner._plan_for

        def faulty(w, bucket):
            plan = plan_for(w, bucket)
            return lambda x: fault(np.array(plan(x)), x, config)

        inner._plan_for = faulty
        return engine

    monkeypatch.setattr(run, "build_engine", build)


def altered(out, x, config):
    """One window answers with its right neighbour's features."""
    out[0, 3, 4] = out[0, 3, 5]
    return out


def pair_dropped(out, x, config):
    """Every window's last pair left uncounted: the answer is the
    reference's from such counts."""
    for i, img in enumerate(np.asarray(x)):
        out[i] = tex.reference(img, config, drop_pair=True)
    return out


@pytest.mark.parametrize("fault", [altered, pair_dropped], ids=lambda f: f.__name__)
def test_fault_makes_the_cell_incorrect(monkeypatch, fault):
    shrink(monkeypatch)
    broken(monkeypatch, fault)
    result = run.run_cell(CELL, 2**40 + 29, 0.3, traced=False)
    assert result["attempted"] > 0 and not result["correct"]
    assert result["failed"] == result["attempted"]
    assert result["checks"]["feature_err"]["value"] > 10 * CONFIG["feature_err_limit"]


def test_bfloat16_control_fails_the_limit():
    raw = np.random.default_rng(11).integers(0, 256, tex.CPU_SHAPE, np.uint8)
    want = tex.reference(raw, CONFIG)
    assert tex.error(ref.to_bfloat16(want), want) > 5 * CONFIG["feature_err_limit"]
    assert tex.error(want.astype(np.float32), want) < CONFIG["feature_err_limit"] / 100


def test_engine_serves_the_features_in_orfeo_order():
    assert tex.FEATURES == ("asm_energy", "entropy", "correlation",
                            "inverse_difference_moment", "contrast",
                            "cluster_shade", "cluster_prominence")
    cell = dict(json.loads((ROOT / f"chipbench/workloads/{CELL}.json").read_text()),
                shape=[12, 11])
    engine = tex.build_engine(cell, dict(CONFIG, expect_backend="onehot"))
    assert engine.cfg.features == tex.FEATURES
    assert engine.spec.scheme == "onehot"
    assert kinds.of(CONFIG) is tex
