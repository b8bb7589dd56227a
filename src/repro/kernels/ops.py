"""jit'd public wrappers around the Pallas kernels.

On CPU (this container) the kernels execute in ``interpret=True`` mode for
correctness validation; on TPU they compile to Mosaic. ``interpret`` is
resolved once per call from the active backend unless forced.

Also exports ``onehot_count`` — the conflict-free counting primitive distilled
from the paper's Scheme 2, in the composable jnp form used inside model code
(MoE router load statistics, token histograms). It is the same math as the
kernel's voting matmul and is tested against ``ref.onehot_count_reference``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.quantize import bin_values
from repro.kernels import ref as _ref
from repro.kernels.glcm_kernel import (
    DEFAULT_CHUNK,
    DEFAULT_COPIES,
    DEFAULT_SLAB_D,
    WINDOW_FEATURES,
    WINDOW_MAX_LEVELS,
    glcm_fused_pallas,
    glcm_volume_pallas,
    glcm_vote_pallas,
    glcm_window_features_pallas,
    glcm_window_pallas,
)
from repro.kernels.histogram_kernel import histogram_pallas

__all__ = [
    "glcm_pallas",
    "glcm_pallas_multi",
    "glcm_pallas_volume",
    "glcm_pallas_window_features",
    "glcm_pallas_windowed",
    "histogram",
    "onehot_count",
    "should_interpret",
]

# VMEM budget of the volume kernel's slab + halo input blocks (both double-
# buffered); half of v5e's default 16 MiB scoped VMEM limit.
_SLAB_VMEM_BYTES = 8 * 1024 * 1024


def should_interpret(interpret: bool | None = None) -> bool:
    """Pallas interpret mode: forced value, else True iff not running on TPU."""
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


def _bin_planes(planes, levels: int, quant, nd: int):
    """Fused-quantize pair planes: bin each sliced plane (never the full
    image) with ``core.quantize.bin_values``.  Per-image (B,) params are
    reshaped to broadcast over the ``nd`` spatial axes."""
    lo = jnp.asarray(quant[0], jnp.float32)
    span = jnp.asarray(quant[1], jnp.float32)
    if lo.ndim:
        bshape = lo.shape + (1,) * nd
        lo = lo.reshape(bshape)
        span = span.reshape(bshape)
    return tuple(bin_values(p, levels, lo, span) for p in planes)


def glcm_pallas(
    img: jax.Array,
    levels: int,
    d: int = 1,
    theta: int = 0,
    *,
    offset: tuple[int, ...] | None = None,
    chunk: int = DEFAULT_CHUNK,
    copies: int = DEFAULT_COPIES,
    interpret: bool | None = None,
    quant=None,
) -> jax.Array:
    """GLCM of quantized image(s) via the pair-stream voting kernel.

    Pair extraction (paper Eq. (2) addressing) happens as fused XLA slices;
    voting happens in the Pallas kernel — which never sees the spatial rank,
    so the same kernel serves images AND volumes. ``img`` is (H, W) →
    (L, L) int32 counts, or (B, H, W) → (B, L, L) computed in one kernel
    launch over a (B, steps) grid; with ``offset=`` (an explicit (dy, dx) or
    (dz, dy, dx) tuple overriding ``(d, theta)``), a (D, H, W) volume or
    (B, D, H, W) stack is voted the same way.

    With ``quant=(lo, span)`` the input is RAW values: the sliced pair
    planes are binned (``core.quantize.bin_values``) on their way into the
    kernel — a quantized full-size image is never materialized.
    """
    off = tuple(int(v) for v in offset) if offset is not None else (
        _ref.glcm_offsets(d, theta)
    )
    nd = len(off)
    if img.ndim not in (nd, nd + 1):
        raise ValueError(
            f"expected a {nd}-D input or a batched {nd + 1}-D stack for "
            f"offset {off}, got shape {img.shape}"
        )
    assoc, rf = _ref.pair_planes_nd(img, off)
    if quant is not None:
        assoc, rf = _bin_planes((assoc, rf), levels, quant, nd)
    lead = img.shape[:-nd]
    return glcm_vote_pallas(
        assoc.reshape(lead + (-1,)).astype(jnp.int32),
        rf.reshape(lead + (-1,)).astype(jnp.int32),
        levels=levels,
        chunk=chunk,
        copies=copies,
        interpret=should_interpret(interpret),
    )


def glcm_pallas_multi(
    img: jax.Array,
    levels: int,
    pairs: tuple[tuple[int, int], ...],
    *,
    tile_h: int | None = None,
    copies: int = 1,
    interpret: bool | None = None,
    quant=None,
) -> jax.Array:
    """Multi-offset GLCM in ONE image pass via the fused tiled kernel.

    ``pairs`` are (d, theta) tuples. ``img`` is (H, W) → (len(pairs), L, L)
    int32, or a (B, H, W) stack → (B, len(pairs), L, L) — the batch rides
    the kernel's leading grid axis, so the whole stack is one launch.
    ``tile_h`` defaults to max(8, largest dy) rounded up to 8.
    """
    offsets = tuple(_ref.glcm_offsets(d, t) for d, t in pairs)
    max_dy = max((dy for dy, _ in offsets), default=1)
    if tile_h is None:
        tile_h = max(8, -(-max_dy // 8) * 8)
    return glcm_fused_pallas(
        img,
        levels=levels,
        offsets=offsets,
        tile_h=tile_h,
        copies=copies,
        interpret=should_interpret(interpret),
        quant=quant,
    )


def glcm_pallas_volume(
    vol: jax.Array,
    levels: int,
    pairs: tuple[tuple[int, int], ...],
    *,
    offsets: tuple[tuple[int, int, int], ...] | None = None,
    slab_d: int | None = None,
    copies: int = 1,
    interpret: bool | None = None,
    quant=None,
) -> jax.Array:
    """Multi-direction 3-D GLCM in ONE volume pass via the depth-slab kernel.

    ``pairs`` are (d, direction) tuples over the 13 unique 3-D directions
    (``ref.DIRECTIONS_3D``); ``offsets`` passes explicit (dz, dy, dx) voxel
    offsets instead. ``vol`` is (D, H, W) → (len(pairs), L, L) int32, or a
    (B, D, H, W) stack → (B, len(pairs), L, L) — the batch rides the
    kernel's leading grid axis, so the whole stack is one launch.
    ``slab_d`` defaults to 8 depth slices, fewer where the plane is so large
    that the double-buffered slab and halo blocks would outgrow
    ``_SLAB_VMEM_BYTES``, and never fewer than the largest dz.
    """
    if offsets is None:
        offsets = tuple(_ref.glcm_offsets_3d(d, k) for d, k in pairs)
    max_dz = max((dz for dz, _, _ in offsets), default=1)
    if slab_d is None:
        h, w = vol.shape[-2:]
        fit = max(1, _SLAB_VMEM_BYTES // (16 * h * w))  # 2 refs × 2 buffers × 4 B
        slab_d = max(max_dz, min(DEFAULT_SLAB_D, fit))
    return glcm_volume_pallas(
        vol,
        levels=levels,
        offsets=tuple(offsets),
        slab_d=slab_d,
        copies=copies,
        interpret=should_interpret(interpret),
        quant=quant,
    )


def glcm_pallas_windowed(
    patches: jax.Array,
    levels: int,
    pairs: tuple[tuple[int, int], ...],
    *,
    copies: int = 1,
    interpret: bool | None = None,
    quant=None,
) -> jax.Array:
    """Per-window GLCMs of an extracted patch grid via the window kernel.

    ``patches`` is (gh, gw, rh, rw) or (B, gh, gw, rh, rw) — the output of
    ``repro.core.schemes.extract_regions`` — and the result appends
    (len(pairs), L, L) to the grid axes. The (B, gh, gw) window grid rides
    the kernel grid, so the full texture map is ONE kernel launch.
    """
    offsets = tuple(_ref.glcm_offsets(d, t) for d, t in pairs)
    return glcm_window_pallas(
        patches,
        levels=levels,
        offsets=offsets,
        copies=copies,
        interpret=should_interpret(interpret),
        quant=quant,
    )


def glcm_pallas_window_features(
    img: jax.Array,
    levels: int,
    pairs: tuple[tuple[int, int], ...],
    window: tuple[int, int],
    features: tuple[str, ...],
    *,
    interpret: bool | None = None,
    quant=None,
) -> jax.Array:
    """Features of every stride-1 ``window``'s symmetric GLCM of image(s)
    via the window-features kernel: (H, W) → (gh, gw, len(pairs),
    len(features)) float32, or (B, H, W) → (B, gh, gw, ...). ``pairs`` are
    (d, theta) tuples; ``features`` names a subset of ``WINDOW_FEATURES``."""
    offsets = tuple(_ref.glcm_offsets(d, t) for d, t in pairs)
    return glcm_window_features_pallas(
        img,
        levels=levels,
        offsets=offsets,
        window=tuple(window),
        features=tuple(features),
        interpret=should_interpret(interpret),
        quant=quant,
    )


def histogram(
    values: jax.Array,
    levels: int,
    *,
    chunk: int = 2048,
    copies: int = 4,
    interpret: bool | None = None,
) -> jax.Array:
    """Exact level counts via the Pallas histogram kernel."""
    return histogram_pallas(
        values,
        levels=levels,
        chunk=chunk,
        copies=copies,
        interpret=should_interpret(interpret),
    )


@functools.partial(jax.jit, static_argnames=("num_classes",))
def onehot_count(
    indices: jax.Array,
    num_classes: int,
    weights: jax.Array | None = None,
) -> jax.Array:
    """Conflict-free (optionally weighted) class counting over the last axis.

    The paper-derived primitive: instead of scatter-adding into a count
    vector (serialized under contention), build the one-hot matrix and
    REDUCE — on TPU this is a matmul/sum the MXU/VPU performs without
    read-modify-write hazards. Shapes: indices (..., K) int → (..., C).
    """
    idx = indices.astype(jnp.int32)
    iota = jax.lax.broadcasted_iota(jnp.int32, idx.shape + (num_classes,), idx.ndim)
    onehot = (idx[..., None] == iota)
    if weights is not None:
        oh = onehot.astype(weights.dtype) * weights[..., None]
    else:
        oh = onehot.astype(jnp.float32)
    return oh.sum(axis=-2)
