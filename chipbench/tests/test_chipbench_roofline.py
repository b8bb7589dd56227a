"""chipbench.roofline: the pair count V against brute force, the work
counts and the peaks table."""

import itertools
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import reference, roofline  # noqa: E402


def brute_pairs(shape, off) -> int:
    """Count (assoc, ref) positions with both inside the array, one by one."""
    n = 0
    for pos in itertools.product(*(range(s) for s in shape)):
        ref = [p + d for p, d in zip(pos, off)]
        n += all(0 <= r < s for r, s in zip(ref, shape))
    return n


@pytest.mark.parametrize("shape,pairs,ndim", [
    ((7, 9), ((1, 0), (1, 45), (4, 0), (4, 45)), 2),
    ((12, 5), ((1, 90), (2, 135), (3, 45)), 2),
    ((5, 6, 7), tuple((1, k) for k in range(13)), 3),
    ((3, 9, 4), ((2, 4), (1, 12), (1, 0)), 3),
])
def test_pairs_match_brute_force(shape, pairs, ndim):
    offs = reference.offsets(pairs, ndim)
    assert roofline.pairs(shape, offs) == sum(brute_pairs(shape, o) for o in offs)


def test_pairs_match_the_oracle_glcm_total():
    from repro.kernels.ref import glcm_reference_nd

    img = np.random.default_rng(0).integers(0, 8, (6, 10, 11))
    offs = reference.offsets(tuple((1, k) for k in range(13)), 3)
    total = sum(int(np.asarray(glcm_reference_nd(img, 8, o)).sum()) for o in offs)
    assert roofline.pairs(img.shape, offs) == total


def test_work_and_least_time():
    offs = reference.offsets(((1, 0), (1, 45), (4, 0), (4, 45)), 2)
    ops, nbytes = roofline.work((16384, 16384), offs, 32, 1)
    assert ops == 2 * 32 * 32 * roofline.pairs((16384, 16384), offs)
    assert nbytes == 16384 * 16384 + 4 * 32 * 32 * 4
    t, bound = roofline.least_time(ops, nbytes, "TPU v5 lite")
    assert bound == "ops" and t == pytest.approx(ops / 393e12)
    t, bound = roofline.least_time(1.0, 819e9, "TPU v5 lite")
    assert bound == "bytes" and t == pytest.approx(1.0)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        roofline.least_time(1.0, 1.0, "cpu")
