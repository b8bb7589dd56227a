"""Stride-1 window features straight from the image: the window-features
kernel (interpret mode) and the plan that serves it, against a plain NumPy
reference that counts every window's pairs by ``np.bincount`` and evaluates
the features in float64; the plan's choice of that path; the two cluster
features; and the engine's answer counters."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.jaxpr_lint import all_avals
from repro.core import backends
from repro.core.haralick import (
    FEATURE_NAMES,
    SELECTABLE_FEATURES,
    haralick_features,
)
from repro.core.plan import compile_plan
from repro.core.spec import GLCMSpec
from repro.kernels.glcm_kernel import WINDOW_FEATURES, glcm_window_features_pallas
from repro.kernels.ref import glcm_offsets
from repro.serve.engine import GLCMEngine, GLCMServeConfig

# Orfeo ToolBox's "simple" set in its band order, less Haralick's
# correlation (equal to ``correlation`` for a symmetric matrix).
OTB = ("asm_energy", "entropy", "correlation", "inverse_difference_moment",
       "contrast", "cluster_shade", "cluster_prominence")


def np_features(p: np.ndarray, names) -> np.ndarray:
    """Features of one normalized (L, L) matrix p[ref, assoc], float64."""
    L = p.shape[0]
    i = np.arange(L, dtype=np.float64)
    ii, jj = np.meshgrid(i, i, indexing="ij")
    px, py = p.sum(1), p.sum(0)
    mux, muy = (i * px).sum(), (i * py).sum()
    sd = np.sqrt(((i - mux) ** 2 * px).sum()) * np.sqrt(((i - muy) ** 2 * py).sum())
    cov = ((ii - mux) * (jj - muy) * p).sum()
    nz = p[p > 0]
    dev = ii + jj - mux - muy
    vals = {
        "asm_energy": (p * p).sum(),
        "contrast": ((ii - jj) ** 2 * p).sum(),
        "correlation": cov / sd if sd > 0 else 0.0,
        "inverse_difference_moment": (p / (1.0 + (ii - jj) ** 2)).sum(),
        "entropy": -(nz * np.log(nz)).sum(),
        "cluster_shade": (dev**3 * p).sum(),
        "cluster_prominence": (dev**4 * p).sum(),
    }
    return np.array([vals[n] for n in names])


def np_window_features(levels_img, levels, offsets, window, names):
    """(gh, gw, n_offsets, n_names) of every stride-1 window's symmetric
    GLCM, counting each window's pairs by np.bincount of its pair codes."""
    h, w = levels_img.shape
    rh, rw = window
    gh, gw = h - rh + 1, w - rw + 1
    q = levels_img.astype(np.int64)
    out = np.zeros((gh, gw, len(offsets), len(names)))
    for y in range(gh):
        for x in range(gw):
            win = q[y:y + rh, x:x + rw]
            for k, (dy, dx) in enumerate(offsets):
                a = win[: rh - dy, max(0, -dx): rw - max(0, dx)]
                r = win[dy:, max(0, dx): rw + min(0, dx)]
                m = np.bincount((r * levels + a).ravel(), minlength=levels * levels)
                m = m.reshape(levels, levels).astype(np.float64)
                m = m + m.T
                out[y, x, k] = np_features(m / m.sum(), names)
    return out


def binned(img, levels, lo=0.0, span=255.0):
    q = np.floor((img.astype(np.float32) - np.float32(lo)) / np.float32(span)
                 * np.float32(levels))
    return np.clip(q, 0, levels - 1).astype(np.int32)


def assert_close(got, want):
    """float32 against float64 within 1e-4 of each value, plus 1e-5 of the
    feature's largest magnitude in the map: cluster shade sums cubes of up
    to (2L − 2)³ that cancel to near zero, which float32 resolves only to a
    few parts in 10⁷ of their size."""
    got = np.asarray(got, np.float64)
    assert got.shape == want.shape
    scale = np.abs(want).reshape(-1, want.shape[-1]).max(0)
    assert np.all(np.abs(got - want) <= 1e-4 * np.abs(want) + 1e-5 * scale)


# (batch, H, W, L, pairs, window, features)
CASES = {
    # the Orfeo ToolBox defaults; W = 23 is no multiple of 128
    "otb_5x5_dx+1_L8": (2, 20, 23, 8, ((1, 135),), (5, 5), OTB),
    # dx of both signs in one launch; vote totals that are no power of two
    "dx-1_dx+2_L8": (2, 19, 30, 8, ((1, 45), (2, 0)), (5, 4),
                     ("correlation", "cluster_prominence", "entropy")),
    # two column chunks and a halo of 4 rows
    "L16_wide": (1, 17, 140, 16, ((2, 135),), (5, 5),
                 ("contrast", "cluster_shade", "correlation")),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_matches_numpy_reference(case):
    b, h, w, levels, pairs, window, names = CASES[case]
    img = np.random.default_rng(len(case)).integers(0, 256, (b, h, w)).astype(np.uint8)
    img[-1, : h // 2] = img[-1, : h // 2] // 64 * 64   # flat windows: σ = 0
    offsets = tuple(glcm_offsets(d, t) for d, t in pairs)
    got = np.asarray(glcm_window_features_pallas(
        jnp.asarray(img), levels=levels, offsets=offsets, window=window,
        features=names, quant=(0.0, 255.0), interpret=True))
    assert got.shape == (b, h - window[0] + 1, w - window[1] + 1, len(pairs), len(names))
    for i in range(b):
        want = np_window_features(binned(img[i], levels), levels, offsets, window,
                                  names)
        assert_close(got[i], want)


def test_kernel_takes_levels_unbatched():
    """quant=None: the input already holds levels; an (H, W) image gives an
    unbatched map."""
    lv = np.random.default_rng(3).integers(0, 8, (12, 13)).astype(np.int32)
    got = glcm_window_features_pallas(
        jnp.asarray(lv), levels=8, offsets=((1, 1),), window=(3, 4),
        features=("entropy", "asm_energy"), interpret=True)
    want = np_window_features(lv, 8, ((1, 1),), (3, 4), ("entropy", "asm_energy"))
    assert_close(got, want)


def test_kernel_rejects_what_it_cannot_compute():
    x = jnp.zeros((16, 16), jnp.int32)
    kw = dict(levels=8, offsets=((1, 1),), window=(5, 5), interpret=True)
    with pytest.raises(ValueError, match="features must name"):
        glcm_window_features_pallas(x, features=("sum_entropy",), **kw)
    with pytest.raises(ValueError, match="exceeds"):
        glcm_window_features_pallas(x, features=("entropy",), **dict(kw, levels=32))
    with pytest.raises(ValueError, match="does not fit"):
        glcm_window_features_pallas(x, features=("entropy",), **dict(kw, window=(1, 5)))


def _window_spec(**kw):
    base = dict(levels=8, pairs=((1, 135),), quantize="uniform", vrange=(0, 255),
                symmetric=True, normalize=True, region="window",
                region_shape=(5, 5), region_stride=(1, 1), scheme="pallas_fused")
    return GLCMSpec(**{**base, **kw})


def test_plan_serves_otb_window_spec_from_the_kernel():
    """The served window plan takes its features from the kernel and agrees
    with the counting plan (onehot: matrices, then the Haralick tail)."""
    img = np.random.default_rng(5).integers(0, 256, (2, 22, 26)).astype(np.uint8)
    direct = compile_plan(_window_spec(), img.shape, features=OTB)
    counted = compile_plan(_window_spec(scheme="onehot"), img.shape, features=OTB)
    assert direct.window_features and not counted.window_features
    got = np.asarray(direct(jnp.asarray(img)))
    want = np.asarray(counted(jnp.asarray(img)), np.float64)
    assert got.shape == (2, 18, 22, 1, 7)
    assert_close(got, want)
    one = compile_plan(_window_spec(), img.shape[1:], features=OTB)
    np.testing.assert_array_equal(np.asarray(one(jnp.asarray(img[0]))), got[0])


def test_direct_plan_has_no_patches_and_no_count_matrices():
    shape = (1, 40, 44)
    plan = compile_plan(_window_spec(), shape, features=OTB)
    jaxpr = jax.make_jaxpr(plan.fn)(jnp.zeros(shape, jnp.uint8))
    gh, gw = 36, 40
    for eqn, aval in all_avals(jaxpr, enter_pallas=False):
        assert tuple(aval.shape[-2:]) != (8, 8), eqn.primitive.name
        assert np.prod(aval.shape) < gh * gw * 25, (eqn.primitive.name, aval.shape)


@pytest.mark.parametrize("change", [
    dict(features=True),
    dict(features=False),
    dict(features=("contrast", "max_correlation_coefficient")),
    dict(features=("sum_entropy",)),
    dict(spec=dict(region="tiles", region_shape=(4, 4), region_stride=None)),
    dict(spec=dict(region_stride=(2, 2))),
    dict(spec=dict(region="global", region_shape=None, region_stride=None)),
    dict(spec=dict(levels=32)),
    dict(spec=dict(symmetric=False)),
    dict(spec=dict(scheme="onehot")),
], ids=["all14", "counts", "with_f14", "unsupported_name", "tiles", "stride2",
        "global", "L32", "one_way", "onehot"])
def test_plan_counts_first_outside_the_kernel_reach(change):
    spec = _window_spec(**change.get("spec", {}))
    features = change.get("features", OTB)
    plan = compile_plan(spec, (1, 24, 24), features=features)
    assert not plan.window_features
    assert not backends.serves_window_features(
        plan.backend, plan.spec, FEATURE_NAMES if features is True else features or ())


def test_fallback_window_plan_still_answers():
    """A window spec that asks for a feature outside the kernel's set keeps
    the counting path, and its columns match the direct path's."""
    img = jnp.asarray(np.random.default_rng(6).integers(0, 256, (1, 14, 15)), jnp.uint8)
    names = ("contrast", "max_correlation_coefficient")
    counted = compile_plan(_window_spec(), img.shape, features=names)
    direct = compile_plan(_window_spec(), img.shape, features=("contrast",))
    assert not counted.window_features and direct.window_features
    a = np.asarray(counted(img))
    assert a.shape == (1, 10, 11, 1, 2)
    np.testing.assert_allclose(a[..., 0], np.asarray(direct(img))[..., 0], rtol=1e-5)


def test_default_features_stay_the_fourteen():
    g = jnp.asarray(np.random.default_rng(7).integers(0, 50, (3, 8, 8)), jnp.float32)
    full = np.asarray(haralick_features(g))
    assert full.shape == (3, 14) and len(FEATURE_NAMES) == 14
    assert SELECTABLE_FEATURES[:14] == FEATURE_NAMES
    np.testing.assert_array_equal(
        full, np.asarray(haralick_features(g, select=FEATURE_NAMES)))
    plan = compile_plan(GLCMSpec(levels=8, scheme="onehot"), (16, 16), features=True)
    out = plan(jnp.asarray(np.random.default_rng(8).integers(0, 8, (16, 16)), jnp.int32))
    assert out.shape == (1, 14)


@pytest.mark.parametrize("symmetric", [True, False])
def test_cluster_features_follow_their_formulas(symmetric):
    m = np.random.default_rng(9).integers(0, 20, (8, 8)).astype(np.float64)
    if symmetric:
        m = m + m.T
    p = m / m.sum()
    names = ("cluster_shade", "cluster_prominence")
    got = np.asarray(haralick_features(jnp.asarray(m, jnp.float32), select=names))
    L = np.arange(8.0)
    mux, muy = (L * p.sum(1)).sum(), (L * p.sum(0)).sum()
    dev = L[:, None] + L[None, :] - mux - muy
    want = [(dev**3 * p).sum(), (dev**4 * p).sum()]
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-5)
    np.testing.assert_allclose(got, np_features(p, names), rtol=2e-5, atol=1e-5)


def test_window_features_are_haralick_definitions():
    """The kernel's set is the selectable Haralick set's, by name."""
    assert set(WINDOW_FEATURES) <= set(SELECTABLE_FEATURES)
    assert set(OTB) == set(WINDOW_FEATURES)


def test_engine_counts_answer_bytes_and_windows():
    spec = _window_spec()
    eng = GLCMEngine(GLCMServeConfig(spec=spec, image_shape=(16, 20), batch_size=2,
                                     features=OTB))
    imgs = np.random.default_rng(10).integers(0, 256, (3, 16, 20)).astype(np.uint8)
    answers = [eng.result(t) for t in [eng.submit(im) for im in imgs]]
    assert all(a.shape == (12, 16, 1, 7) for a in answers)
    w = eng.stats()["workloads"][0]
    assert w["windows"] == 3 * 12 * 16
    # a full batch of two, then the third alone in the bucket of one
    assert w["answer_bytes"] == 3 * 12 * 16 * 7 * 4
    whole = GLCMEngine(GLCMServeConfig(levels=8, image_shape=(16, 16), batch_size=1,
                                       features=True))
    whole.result(whole.submit(imgs[0, :, :16]))
    w = whole.stats()["workloads"][0]
    assert w["windows"] == 1 and w["answer_bytes"] == 4 * 14 * 4  # 4 offsets
