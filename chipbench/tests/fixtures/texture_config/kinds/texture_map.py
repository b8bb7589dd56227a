"""Dense texture maps: one answer per request of (gh, gw, n_offsets,
n_features) Haralick features, one GLCM per sliding window of the
configuration's ``region_shape`` at its ``region_stride``, binned over the
fixed range ``vrange``, in the manner of Orfeo ToolBox's
HaralickTextureExtraction. The features are those of its "simple" set that
the program computes. The reference counts window by window with
``chipbench.reference.counts``."""

from __future__ import annotations

import numpy as np

from chipbench import data, kinds, roofline
from chipbench import reference as ref

FEATURES = ("asm_energy", "entropy", "correlation", "inverse_difference_moment",
            "contrast")
FLOOR = 1e-3


def make_pool(pool, shape, seed):
    return data.make_pool(pool, shape, seed)


def build_engine(cell, config):
    return kinds.serve_engine(cell, config, features=FEATURES)


def _grid(shape, spec):
    return [(n - r) // s + 1
            for n, r, s in zip(shape, spec["region_shape"], spec["region_stride"])]


def work(cell, config, pool):
    """ops = 2 L^2 V over every window's in-bounds pairs; bytes = the raw
    request in and int32 counts of every window out."""
    spec = config["spec"]
    offs = ref.offsets(spec["pairs"], spec["ndim"])
    windows = int(np.prod(_grid(cell["shape"], spec)))
    levels = spec["levels"]
    ops = 2 * levels * levels * windows * roofline.pairs(spec["region_shape"], offs)
    nbytes = (int(np.prod(cell["shape"])) * pool[0].dtype.itemsize
              + windows * len(offs) * levels * levels * 4)
    return ops, nbytes


def reference(raw, config):
    spec = config["spec"]
    levels = spec["levels"]
    lo, hi = (np.float32(v) for v in spec["vrange"])
    q = np.floor((raw.astype(np.float32) - lo) / (hi - lo) * np.float32(levels))
    q = np.clip(q, 0, levels - 1).astype(np.uint8)
    offs = ref.offsets(spec["pairs"], spec["ndim"])
    (rh, rw), (sh, sw) = spec["region_shape"], spec["region_stride"]
    gh, gw = _grid(raw.shape, spec)
    pick = [ref.FEATURE_NAMES.index(f) for f in FEATURES]
    out = np.zeros((gh, gw, len(offs), len(FEATURES)))
    for i in range(gh):
        for j in range(gw):
            m = ref.counts(q[i * sh:i * sh + rh, j * sw:j * sw + rw], levels, offs)
            m = m.astype(np.float64)
            if spec["symmetric"]:
                m = m + np.swapaxes(m, -1, -2)
            if spec["normalize"]:
                m = m / np.maximum(m.sum(axis=(-2, -1), keepdims=True), 1.0)
            out[i, j] = ref.features(m)[..., pick]
    return out


def _terms(got, want):
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return None
    return np.abs(got - want) / (np.abs(want) + FLOOR)


def error(got, want):
    terms = _terms(got, want)
    return float("inf") if terms is None else float(terms.max())


def worst(got, want):
    terms = _terms(got, want)
    if terms is None:
        return "a misshapen or non-finite answer"
    i, j, k, f = np.unravel_index(int(np.argmax(terms)), terms.shape)
    return f"window ({i}, {j}), offset {k}, {FEATURES[f]}"


def cpu_cell(cell, config):
    return cell, "onehot"
