"""Plain NumPy reference of a served GLCM answer, and the comparison with it.

The served answer of one request is the Haralick feature matrix
(n_offsets, 14) of its image or volume: uniform quantization over the
request's own value range, pair counting per offset, optional symmetrize
and normalize, then the fourteen features. This module computes the same
from the raw request alone, with nothing taken from the program: its own
offset tables, its own float32 binning affine, exact int64 counts by
``np.bincount``, and the features in float64.

``features(..., rnd=to_bfloat16)`` is the control: the same formulas with
every intermediate rounded to bfloat16 (sums accumulated wide, as a TPU
does), the precision step below the float32 the configurations state.

``feature_error`` is the number that decides ``correct``: the largest
``|got - want| / (|want| + FLOOR)`` over every request, offset and feature.
``info_correlation_2`` and ``max_correlation_coefficient`` are square roots
of quantities that vanish on uncorrelated textures, where the root turns a
rounding error e of the radicand into sqrt(e); both are compared squared.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

FEATURE_NAMES = (
    "asm_energy",
    "contrast",
    "correlation",
    "variance",
    "inverse_difference_moment",
    "sum_average",
    "sum_variance",
    "sum_entropy",
    "entropy",
    "difference_variance",
    "difference_entropy",
    "info_correlation_1",
    "info_correlation_2",
    "max_correlation_coefficient",
)
SQUARED = (12, 13)
FLOOR = 1e-3
_EPS = 1e-12
_TINY = float(np.finfo(np.float32).tiny)

# (dy, dx) per theta for 2-D pairs (d, theta): ref pixel = assoc + d*(dy, dx).
OFFSETS_2D = {0: (0, 1), 45: (1, -1), 90: (1, 0), 135: (1, 1)}
# The 13 unique 3-D directions (dz, dy, dx), indexed by a 3-D pair's second
# element: 0..3 are the in-plane thetas, 4..12 the dz = +1 directions.
DIRECTIONS_3D = (
    (0, 0, 1), (0, 1, -1), (0, 1, 0), (0, 1, 1),
    (1, -1, -1), (1, -1, 0), (1, -1, 1), (1, 0, -1), (1, 0, 0), (1, 0, 1),
    (1, 1, -1), (1, 1, 0), (1, 1, 1),
)


def offsets(pairs, ndim: int) -> tuple[tuple[int, ...], ...]:
    """Per-axis voxel offsets of the (d, direction) pairs."""
    table = OFFSETS_2D if ndim == 2 else dict(enumerate(DIRECTIONS_3D))
    return tuple(tuple(d * c for c in table[t]) for d, t in pairs)


def pair_slices(dims, offset):
    """(assoc, ref) index tuples of the in-bounds pairs at ``offset``."""
    assoc, ref = [], []
    for delta, size in zip(offset, dims):
        if delta >= 0:
            assoc.append(slice(0, size - delta))
            ref.append(slice(delta, size))
        else:
            assoc.append(slice(-delta, size))
            ref.append(slice(0, size + delta))
    return tuple(assoc), tuple(ref)


def quantize(x: np.ndarray, levels: int) -> np.ndarray:
    """Uniform binning over the array's own range, in float32 as served:
    floor((x - lo) / span * L), clipped to [0, L). Integer inputs of up to
    16 bits go through a table of that expression over every value of
    their type."""
    lo = np.float32(x.min())
    span = np.maximum(np.float32(x.max()) - lo, np.float32(_TINY))

    def binned(v):
        q = np.floor((v.astype(np.float32) - lo) / span * np.float32(levels))
        return np.clip(q, 0, levels - 1).astype(np.uint8)

    if x.dtype.kind in "iu" and x.dtype.itemsize <= 2:
        utype = np.dtype(f"u{x.dtype.itemsize}")
        table = binned(np.arange(2 ** (8 * x.dtype.itemsize), dtype=utype).view(x.dtype))
        return table[x.view(utype)]
    return binned(x)


def counts(q: np.ndarray, levels: int, offs) -> np.ndarray:
    """(n_offsets, L, L) int64 counts: P[ref_level, assoc_level]. Blocks of
    leading-axis rows are counted on a pool of threads and summed."""
    cells = levels * levels
    n0 = q.shape[0]
    step = max(1, (1 << 22) // max(1, int(np.prod(q.shape[1:]))))

    def block(k, r0, r1):
        off = offs[k]
        a_ix, r_ix = pair_slices(q.shape[1:], off[1:])
        a = q[(slice(r0, r1),) + a_ix]
        r = q[(slice(r0 + off[0], r1 + off[0]),) + r_ix]
        key = r.astype(np.uint16) * levels + a
        return k, np.bincount(key.ravel(), minlength=cells)

    tasks = []
    for k, off in enumerate(offs):
        if off[0] < 0:
            raise ValueError(f"offset {off}: the leading delta must be >= 0")
        tasks += [(k, r0, min(r0 + step, n0 - off[0]))
                  for r0 in range(0, n0 - off[0], step)]
    out = np.zeros((len(offs), cells), np.int64)
    with ThreadPoolExecutor(os.cpu_count()) as ex:
        for k, c in ex.map(lambda t: block(*t), tasks):
            out[k] += c
    return out.reshape(len(offs), levels, levels)


def identity(x):
    return x


def to_bfloat16(x):
    """Round to bfloat16 and back: one bfloat16 result of the control."""
    import ml_dtypes

    return np.asarray(x, np.float64).astype(ml_dtypes.bfloat16).astype(np.float64)


def features(mats: np.ndarray, rnd=identity) -> np.ndarray:
    """(..., L, L) counts or probabilities → (..., 14) features.

    ``rnd`` rounds every intermediate result (``identity`` for the float64
    reference, ``to_bfloat16`` for the control)."""
    flat = np.asarray(mats, np.float64).reshape((-1,) + mats.shape[-2:])
    out = np.stack([_single(m, rnd) for m in flat])
    return out.reshape(mats.shape[:-2] + (len(FEATURE_NAMES),))


def _single(m: np.ndarray, r) -> np.ndarray:
    L = m.shape[-1]

    def s(x, axis=None):
        return r(np.sum(x, axis=axis))

    def ent(x):
        return r(-s(r(x * r(np.log(r(x + _EPS))))))

    p = r(r(m) / max(s(r(m)), _EPS))
    i = np.arange(L, dtype=np.float64)
    ii, jj = np.meshgrid(i, i, indexing="ij")
    px, py = s(p, 1), s(p, 0)
    mu_x, mu_y = s(r(i * px)), s(r(i * py))
    sd_x = r(np.sqrt(max(s(r(r((i - mu_x) ** 2) * px)), 0.0)))
    sd_y = r(np.sqrt(max(s(r(r((i - mu_y) ** 2) * py)), 0.0)))
    ks = np.arange(2 * L - 1, dtype=np.float64)
    p_sum = r(np.bincount((ii + jj).astype(np.int64).ravel(), p.ravel(), 2 * L - 1))
    p_diff = r(np.bincount(np.abs(ii - jj).astype(np.int64).ravel(), p.ravel(), L))

    f1 = s(r(p * p))
    f2 = s(r((ii - jj) ** 2 * p))
    cov = s(r(r(r(ii - mu_x) * r(jj - mu_y)) * p))
    f3 = r(cov / max(r(sd_x * sd_y), _EPS))
    mu = s(r(p * ii))
    f4 = s(r(r(r(ii - mu) ** 2) * p))
    f5 = s(r(p / (1.0 + (ii - jj) ** 2)))
    f6 = s(r(ks * p_sum))
    f8 = ent(p_sum)
    f7 = s(r(r(r(ks - f6) ** 2) * p_sum))
    f9 = ent(p)
    kd = np.arange(L, dtype=np.float64)
    diff_mean = s(r(kd * p_diff))
    f10 = s(r(r(r(kd - diff_mean) ** 2) * p_diff))
    f11 = ent(p_diff)
    hx, hy = ent(px), ent(py)
    pxy = r(px[:, None] * py[None, :])
    hxy1 = r(-s(r(p * r(np.log(r(pxy + _EPS))))))
    hxy2 = ent(pxy)
    f12 = r(r(f9 - hxy1) / max(hx, hy, _EPS))
    f13 = r(np.sqrt(max(r(1.0 - r(np.exp(r(-2.0 * r(hxy2 - f9))))), 0.0)))
    a = r(p / r(np.sqrt(r(np.maximum(px[:, None], _EPS) * np.maximum(py[None, :], _EPS)))))
    eig = np.linalg.eigvalsh(r(a @ a.T))
    f14 = r(np.sqrt(max(r(np.sort(eig)[-2]), 0.0)))
    return np.array([f1, f2, f3, f4, f5, f6, f7, f8, f9, f10, f11, f12, f13, f14],
                    np.float64)


def raw_counts(raw: np.ndarray, cfg: dict) -> np.ndarray:
    """Exact (n_offsets, L, L) counts of one raw request under a
    configuration file's ``spec``."""
    spec = cfg["spec"]
    q = quantize(raw, spec["levels"])
    return counts(q, spec["levels"], offsets(spec["pairs"], raw.ndim))


def answer(mats: np.ndarray, cfg: dict, rnd=identity) -> np.ndarray:
    """The answer (n_offsets, 14) from exact counts: symmetrize and
    normalize where the ``spec`` says, then the features."""
    spec = cfg["spec"]
    mats = rnd(mats.astype(np.float64))
    if spec.get("symmetric"):
        mats = rnd(mats + np.swapaxes(mats, -1, -2))
    if spec.get("normalize"):
        mats = rnd(mats / np.maximum(mats.sum(axis=(-2, -1), keepdims=True), 1.0))
    return features(mats, rnd)


def feature_error(got, want) -> float:
    """max |got - want| / (|want| + FLOOR) over all entries, with the two
    square-root features compared squared; inf for a non-finite or
    misshapen answer."""
    got = np.array(got, np.float64)
    want = np.array(want, np.float64)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return float("inf")
    got[..., SQUARED] **= 2
    want[..., SQUARED] **= 2
    return float(np.max(np.abs(got - want) / (np.abs(want) + FLOOR)))


def worst_entry(got, want) -> tuple[int, int]:
    """(offset, feature) index of the largest ``feature_error`` term."""
    got = np.array(got, np.float64)
    want = np.array(want, np.float64)
    got[..., SQUARED] **= 2
    want[..., SQUARED] **= 2
    err = np.abs(got - want) / (np.abs(want) + FLOOR)
    err = np.where(np.isfinite(err), err, np.inf)
    k, f = np.unravel_index(int(np.argmax(err)), err.shape)
    return int(k), int(f)
