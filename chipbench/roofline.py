"""The least time a GLCM kernel could take on a chip, from the problem alone.

The work is counted from the request's shape, the spec's L and its offsets,
never from how a kernel implements the vote, so the share reads the same
whatever the kernel does:

  pairs(shape, offsets)   V: the in-bounds voxel pairs summed over offsets
  ops = 2 · L² · V        the dense one-hot form of the vote: each pair is
                          one rank-1 update of an (L, L) matrix, counted as
                          L² multiply-adds (int8 operands)
  bytes = raw request bytes in + int32 (n_offsets, L, L) counts out

The least time is the larger of ops / int8 peak and bytes / HBM bandwidth;
``bound`` names which of the two it is. A kernel's roofline share is that
least time over the kernel's measured device time.
"""

from __future__ import annotations

import math

# Published peaks of one chip, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (system architecture):
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None


def pairs(shape, offsets) -> int:
    """V: in-bounds (assoc, ref) pairs of one request, summed over offsets."""
    return sum(math.prod(max(n - abs(d), 0) for n, d in zip(shape, off))
               for off in offsets)


def work(shape, offsets, levels: int, itemsize: int) -> tuple[int, int]:
    """(ops, bytes) of one request."""
    ops = 2 * levels * levels * pairs(shape, offsets)
    nbytes = math.prod(shape) * itemsize + len(offsets) * levels * levels * 4
    return ops, nbytes


def least_time(ops: float, nbytes: float, device_kind: str) -> tuple[float, str]:
    """(seconds, "ops" | "bytes"): the least time and the term that sets it."""
    pk = peaks(device_kind)
    t_ops = ops / pk["int8_ops_per_s"]
    t_bytes = nbytes / pk["hbm_bytes_per_s"]
    return (t_ops, "ops") if t_ops >= t_bytes else (t_bytes, "bytes")
