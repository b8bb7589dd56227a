"""Pallas TPU kernels for GLCM voting — the paper's contribution, TPU-native.

Every kernel votes with the same **row-wise one-hot MXU matmul**. A row of
W pixels is kept as it sits in the vector registers: pixels on the 128-wide
lane axis, one row per sublane. Its associate levels ``a`` and reference
levels ``r`` (both (1, W) int32) become lane-major one-hots
``A, R ∈ {0,1}^(L×W)`` (levels on sublanes, built by an iota compare on the
VPU), and the row's sub-GLCM is ``R Aᵀ`` — an int8 matmul contracting over
the lanes, accumulated in int32 on the MXU. The CUDA atomicAdd of the
paper's Scheme 1 is thereby replaced by a reduction along the systolic axis:
many pairs voting one bin is a sum, not a serialized conflict. No tile is
ever flattened into a 1-D pair stream (Mosaic's (8, 128) vector layout has
no cheap 2-D → 1-D relayout); rows are read straight from the VMEM blocks.

A level of -1 is the **dead vote**: its one-hot column is all zero, so the
pair is dropped. Every out-of-image, out-of-window or padded position is
masked to -1 instead of being sliced away, which keeps every vector at its
full, tile-aligned width.

The level axis of the one-hots and of the (L, L) accumulators is padded to
a multiple of 8 (the int32 sublane tile); the wrappers slice the padding
off. Rows wider than ``_ONEHOT_ELEMS / L_pad`` lanes are voted in lane
chunks so the one-hot intermediates stay a few hundred KiB of VMEM.

``copies`` is the paper's R (Scheme 2, Eq. (5)/(6)): each grid step's rows
are split into R contiguous sub-streams, each voting into a private (L, L)
accumulator, summed before leaving the kernel.

Four kernels:

``glcm_vote_pallas`` — pair-stream voting. Pre-formed (assoc, ref) streams
    are laid out as (B, rows, chunk/8) so each grid step holds an (8, chunk/8)
    block of each stream (a legal (8, 128)-or-full-dim block).

``glcm_fused_pallas`` — beyond-paper fusion for whole images: one pass over
    the image computes GLCMs for MULTIPLE (dy, dx) offsets, reusing each row's
    associate one-hot across offsets. The halo of paper Eq. (8)/(9) is a
    second input Ref whose ``index_map`` points at the *next* row tile; the
    Pallas grid pipeline double-buffers the HBM→VMEM tile DMA against the
    voting — the two-stream timeline of paper Fig. 3, made structural.
    Column offsets are lane rotations (``pltpu.roll``) plus an edge mask.

``glcm_window_pallas`` — region-structured voting: the extracted
    (B, gh, gw, rh, rw) patch grid rides the kernel grid, one grid cell per
    window, each voting its patch's multi-offset GLCM into its own output
    block (windows are independent, so no cross-step accumulation).

``glcm_volume_pallas`` — volumes: a (B, D, H, W) stack is processed as a
    grid over ``(B, n_slabs)`` depth slabs, each voting all requested 3-D
    directions at once; the inter-slice halo (dz > 0) is the NEXT slab,
    DMA'd via a second input Ref exactly like the fused kernel's next tile.

The accumulating kernels carry a **batch grid axis**: the grid is
(B, steps) and the output ``index_map`` pins each image's accumulator to its
batch slot, so a stack is processed in ONE ``pallas_call`` launch. Grid
iteration is sequential with the LAST axis innermost, so the constant
output block acts as a revisited accumulator: zeroed at step 0 of that
image and incremented by every later step.

With ``quant=(lo, span)`` the fused, window and volume kernels take RAW
values and bin each row in-register (the same f32 op sequence as
``core.quantize.bin_values``); the per-image (lo, span) table lives in SMEM
and is indexed by the batch grid position.

Accumulation is int32 (int8 one-hot matmuls with ``preferred_element_type=
int32``), so counts are exact up to 2³¹ — f32 accumulation would silently
round past 2²⁴ on gigapixel images.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "glcm_vote_pallas",
    "glcm_fused_pallas",
    "glcm_window_pallas",
    "glcm_volume_pallas",
    "DEFAULT_CHUNK",
    "DEFAULT_COPIES",
    "DEFAULT_SLAB_D",
]

DEFAULT_CHUNK = 2048   # pairs per grid step of the pair-stream kernel
DEFAULT_COPIES = 4     # R, the paper's copy count
DEFAULT_SLAB_D = 8     # depth slices per slab of the volume kernel

_LEVEL_TILE = 8               # int32 sublane tile: level-axis padding
_ONEHOT_ELEMS = 64 * 1024     # cap on L_pad × lane-chunk per one-hot
_STREAM_ROWS = 8              # sublane rows per pair-stream block


def _padded_levels(levels: int) -> int:
    """The one-hot / accumulator level extent: ``levels`` rounded up to 8."""
    return -(-levels // _LEVEL_TILE) * _LEVEL_TILE


def _lane_chunk(width: int, lp: int) -> int:
    """Lanes voted per one-hot: the whole row, or the largest multiple of 128
    dividing ``width`` that keeps an (lp, chunk) one-hot under the cap."""
    if width % 128:
        return width
    cw = max(128, min(width, _ONEHOT_ELEMS // lp) // 128 * 128)
    while width % cw:
        cw -= 128
    return cw


def _bin(x: jax.Array, levels: int, quant) -> jax.Array:
    """Raw row → int32 levels with the same op sequence as
    ``core.quantize.bin_values`` (f32 affine, floor, clip, int32 cast), so
    fused-quantize kernel plans are bit-exact with quantize-then-count;
    ``quant=None`` means the row already holds levels."""
    if quant is None:
        return x
    lo, span = quant
    q = jnp.floor((x.astype(jnp.float32) - lo) / span * levels)
    return jnp.clip(q, 0, levels - 1).astype(jnp.int32)


def _quant_table(quant, b: int) -> jax.Array:
    """Normalize a (lo, span) pair — python floats or per-image (B,) arrays —
    into the (B, 2) f32 SMEM table the kernels index by batch position."""
    lo = jnp.broadcast_to(jnp.asarray(quant[0], jnp.float32).reshape(-1), (b,))
    span = jnp.broadcast_to(jnp.asarray(quant[1], jnp.float32).reshape(-1), (b,))
    return jnp.stack([lo, span], axis=1)


def _quant_spec() -> pl.BlockSpec:
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _shift_mask(row: jax.Array, dx: int, ok, width: int) -> jax.Array:
    """The reference row seen from each associate column: ``row[x + dx]``,
    or -1 (dead vote) where ``x + dx`` leaves [0, width) or ``ok`` is
    False. The shift is a lane rotation, skipped when dx == 0."""
    if dx % row.shape[1]:
        row = pltpu.roll(row, (-dx) % row.shape[1], 1)
    col = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
    valid = jnp.logical_and(ok, (col + dx >= 0) & (col + dx < width))
    return jnp.where(valid, row, -1)


def _onehot(v: jax.Array, lp: int) -> jax.Array:
    """(1, W) int32 levels → (lp, W) int8 lane-major one-hot; -1 → zero
    column (the dead vote)."""
    iota = jax.lax.broadcasted_iota(jnp.int32, (lp, v.shape[1]), 0)
    return (iota == v).astype(jnp.int8)


def _vote_row(a: jax.Array, rs, lp: int, cw: int, acc):
    """Add one row's votes: associate row ``a`` against each reference row
    of ``rs`` (all (1, W) int32) into the matching (lp, lp) accumulator of
    ``acc``. The associate one-hot is built once per lane chunk and shared
    by every offset."""
    acc = list(acc)
    for c0 in range(0, a.shape[1], cw):
        oa = _onehot(a[:, c0 : c0 + cw], lp)
        for k, r in enumerate(rs):
            orf = _onehot(r[:, c0 : c0 + cw], lp)
            acc[k] = acc[k] + jax.lax.dot_general(
                orf, oa, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.int32,
            )
    return tuple(acc)


def _accumulate(n_rows: int, copies: int, row_fn, n_out: int, lp: int):
    """Σ_y row_fn over rows [0, n_rows), split into ``copies`` contiguous
    sub-streams with private accumulators (paper Eq. (5)/(6)), summed."""
    zero = tuple(jnp.zeros((lp, lp), jnp.int32) for _ in range(n_out))
    total = zero
    for c in range(copies):
        lo, hi = n_rows * c // copies, n_rows * (c + 1) // copies
        if hi > lo:
            part = jax.lax.fori_loop(lo, hi, row_fn, zero)
            total = tuple(t + p for t, p in zip(total, part))
    return total


# ---------------------------------------------------------------------------
# Kernel 1: pair-stream voting (grid = (B, steps))
# ---------------------------------------------------------------------------

def _vote_kernel(a_ref, r_ref, o_ref, *, lp: int, copies: int, cw: int):
    # Steps are the innermost grid axis: step 0 of each image zeroes that
    # image's accumulator block before any votes land in it.
    @pl.when(pl.program_id(1) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    def row_fn(y, acc):
        return _vote_row(
            a_ref[0, pl.ds(y, 1), :], (r_ref[0, pl.ds(y, 1), :],), lp, cw, acc
        )

    (acc,) = _accumulate(a_ref.shape[1], copies, row_fn, 1, lp)
    o_ref[0, :, :] += acc


@functools.partial(
    jax.jit, static_argnames=("levels", "chunk", "copies", "interpret")
)
def glcm_vote_pallas(
    assoc: jax.Array,
    ref: jax.Array,
    *,
    levels: int,
    chunk: int = DEFAULT_CHUNK,
    copies: int = DEFAULT_COPIES,
    interpret: bool = False,
) -> jax.Array:
    """Vote (assoc, ref) pair streams into GLCMs (int32).

    Inputs are int32 of equal shape — either 1-D ``(N,)`` (one stream →
    ``(L, L)``) or 2-D ``(B, N)`` (one stream per image → ``(B, L, L)``,
    computed in a single kernel launch over a ``(B, steps)`` grid). Entries
    of -1 are padding and do not vote. Each grid step votes an
    (8, ⌈chunk/8⌉) block of each stream; streams are padded to whole steps.
    """
    if assoc.shape != ref.shape or assoc.ndim not in (1, 2):
        raise ValueError(
            f"pair streams must be equal 1-D or 2-D, got {assoc.shape} vs {ref.shape}"
        )
    if chunk % copies:
        raise ValueError(f"chunk ({chunk}) must be divisible by copies ({copies})")
    batched = assoc.ndim == 2
    a = assoc.astype(jnp.int32).reshape(-1 if not batched else (assoc.shape[0], -1))
    r = ref.astype(jnp.int32).reshape(a.shape)
    if not batched:
        a = a[None, :]
        r = r[None, :]
    b, n = a.shape
    lanes = -(-chunk // _STREAM_ROWS)
    per_step = _STREAM_ROWS * lanes
    pad = (-n) % per_step if n else per_step
    a = jnp.pad(a, ((0, 0), (0, pad)), constant_values=-1)
    r = jnp.pad(r, ((0, 0), (0, pad)), constant_values=-1)
    steps = a.shape[1] // per_step
    a = a.reshape(b, steps * _STREAM_ROWS, lanes)
    r = r.reshape(b, steps * _STREAM_ROWS, lanes)
    lp = _padded_levels(levels)

    block = pl.BlockSpec((1, _STREAM_ROWS, lanes), lambda bi, i: (bi, i, 0))
    out = pl.pallas_call(
        functools.partial(
            _vote_kernel, lp=lp, copies=copies, cw=_lane_chunk(lanes, lp)
        ),
        grid=(b, steps),
        in_specs=[block, block],
        out_specs=pl.BlockSpec((1, lp, lp), lambda bi, i: (bi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, lp, lp), jnp.int32),
        interpret=interpret,
    )(a, r)
    out = out[:, :levels, :levels]
    return out if batched else out[0]


# ---------------------------------------------------------------------------
# Kernel 2: fused tiled image kernel — multi-offset, halo via next-tile Ref,
# batch of images as the leading grid axis
# ---------------------------------------------------------------------------

def _fused_kernel(
    *refs,
    levels: int,
    lp: int,
    copies: int,
    offsets: tuple[tuple[int, int], ...],
    tile_h: int,
    width: int,
    height: int,
    cw: int,
    fused_quant: bool = False,
):
    # refs is (cur, nxt, o) for pre-quantized input, or (cur, nxt, q, o)
    # when quantization is fused: q is the (B, 2) = (lo, span) SMEM table
    # and the raw f32 rows are binned IN-REGISTER — the quantized image
    # never exists in HBM.
    cur_ref, nxt_ref, o_ref = refs[0], refs[1], refs[-1]
    step = pl.program_id(1)  # row-tile step within the current image

    @pl.when(step == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    quant = None
    if fused_quant:
        bi = pl.program_id(0)
        quant = (refs[2][bi, 0], refs[2][bi, 1])
    row0 = step * tile_h  # global row of this tile's first row

    def load(ref, y):
        return ref[0, pl.ds(y, 1), :]

    def row_fn(y, acc):
        a = _shift_mask(_bin(load(cur_ref, y), levels, quant), 0,
                        row0 + y < height, width)
        rs = []
        for dy, dx in offsets:  # static unroll over directions
            # Reference row y + dy: in this tile, or in the halo tile.
            yy = y + dy
            if dy == 0:
                raw = load(cur_ref, y)
            elif dy == tile_h:
                raw = load(nxt_ref, y)
            else:
                raw = jnp.where(
                    yy < tile_h,
                    load(cur_ref, jnp.minimum(yy, tile_h - 1)),
                    load(nxt_ref, jnp.maximum(yy - tile_h, 0)),
                )
            rs.append(_shift_mask(_bin(raw, levels, quant), dx,
                                  row0 + yy < height, width))
        return _vote_row(a, rs, lp, cw, acc)

    acc = _accumulate(tile_h, copies, row_fn, len(offsets), lp)
    for k in range(len(offsets)):
        o_ref[0, k, :, :] += acc[k]


@functools.partial(
    jax.jit,
    static_argnames=("levels", "offsets", "tile_h", "copies", "interpret"),
)
def glcm_fused_pallas(
    img: jax.Array,
    *,
    levels: int,
    offsets: tuple[tuple[int, int], ...],
    tile_h: int = 8,
    copies: int = 1,
    interpret: bool = False,
    quant=None,
) -> jax.Array:
    """One pass over quantized image(s) → multi-offset GLCMs (int32).

    ``img`` is (H, W) → (n_offsets, L, L), or (B, H, W) → (B, n_offsets,
    L, L); the batch is the leading grid axis, so all B images are processed
    by ONE kernel launch with the per-image accumulator selected by the
    output ``index_map``.

    With ``quant=(lo, span)`` (python floats, or per-image (B,) arrays) the
    input is RAW values: each f32 row is binned in-register by the same
    affine as ``core.quantize.bin_values`` before voting, so the quantized
    image is never materialized. Padded rows are masked by the row index,
    so raw pad values never vote.

    ``offsets`` are (dy, dx) pixel offsets (see ``kernels.ref.glcm_offsets``);
    every dy must satisfy 0 <= dy <= tile_h so the halo fits in the next row
    tile. Image height is padded to a tile multiple (padded rows masked).
    On TPU ``tile_h`` must be a multiple of 8 (or the padded height). The
    full image width is kept resident per tile: the VMEM working set is
    two double-buffered (tile_h, W) tiles plus a few (L_pad, chunk) one-hots
    — independent of B and H, which only advance the DMA source.
    """
    if img.ndim not in (2, 3):
        raise ValueError(f"expected (H, W) or (B, H, W) image, got {img.shape}")
    batched = img.ndim == 3
    h, w = img.shape[-2:]
    for dy, dx in offsets:
        if not (0 <= dy <= tile_h):
            raise ValueError(f"dy={dy} must be in [0, tile_h={tile_h}]")
        if abs(dx) >= w:
            raise ValueError(f"|dx|={abs(dx)} must be < width={w}")
    imgs = img.astype(jnp.float32 if quant is not None else jnp.int32)
    if not batched:
        imgs = imgs[None]
    pad_h = (-h) % tile_h
    imgp = jnp.pad(imgs, ((0, 0), (0, pad_h), (0, 0)), constant_values=-1)
    b, hp, _ = imgp.shape
    steps = hp // tile_h
    n_off = len(offsets)
    lp = _padded_levels(levels)

    in_specs = [
        pl.BlockSpec((1, tile_h, w), lambda bi, i: (bi, i, 0)),
        # Halo: the NEXT row tile of the SAME image (clamped at the
        # bottom; the clamp is safe because rows >= height are masked
        # in-kernel).
        pl.BlockSpec(
            (1, tile_h, w), lambda bi, i: (bi, jnp.minimum(i + 1, steps - 1), 0)
        ),
    ]
    args = [imgp, imgp]
    if quant is not None:
        # The (B, 2) (lo, span) table — the ONLY quantization state a fused
        # plan materializes — sits whole in SMEM.
        in_specs.append(_quant_spec())
        args.append(_quant_table(quant, b))

    out = pl.pallas_call(
        functools.partial(
            _fused_kernel,
            levels=levels,
            lp=lp,
            copies=copies,
            offsets=tuple(offsets),
            tile_h=tile_h,
            width=w,
            height=h,
            cw=_lane_chunk(w, lp),
            fused_quant=quant is not None,
        ),
        grid=(b, steps),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, n_off, lp, lp), lambda bi, i: (bi, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, n_off, lp, lp), jnp.int32),
        interpret=interpret,
    )(*args)
    out = out[..., :levels, :levels]
    return out if batched else out[0]


# ---------------------------------------------------------------------------
# Kernel 3: region-structured voting — the window grid IS the kernel grid
# ---------------------------------------------------------------------------

def _window_kernel(
    *refs,
    levels: int,
    lp: int,
    copies: int,
    offsets: tuple[tuple[int, int], ...],
    rh: int,
    rw: int,
    cw: int,
    fused_quant: bool = False,
):
    # One grid cell per (batch, window-row, window-col): this cell's patch is
    # in VMEM and its output block is private, so the whole GLCM is produced
    # by straight assignment — no @pl.when init, no revisited accumulator.
    # refs is (p, o), or (p, q, o) when quantization is fused — q is the
    # (B, 2) SMEM table of image-level (lo, span) (windows share their
    # image's range).
    p_ref, o_ref = refs[0], refs[-1]
    quant = None
    if fused_quant:
        bi = pl.program_id(0)
        quant = (refs[1][bi, 0], refs[1][bi, 1])

    def load(y):
        return _bin(p_ref[0, 0, 0, pl.ds(y, 1), :], levels, quant)

    def row_fn(y, acc):
        # Intra-window pairs (paper Eq. (2) addressing, region-local):
        # pairs never cross a window boundary, by the workload's definition.
        a = load(y)
        rs = [
            _shift_mask(load(jnp.minimum(y + dy, rh - 1)) if dy else a, dx,
                        y + dy < rh, rw)
            for dy, dx in offsets
        ]
        return _vote_row(a, rs, lp, cw, acc)

    acc = _accumulate(rh, copies, row_fn, len(offsets), lp)
    for k in range(len(offsets)):
        o_ref[0, 0, 0, k, :, :] = acc[k]


@functools.partial(
    jax.jit, static_argnames=("levels", "offsets", "copies", "interpret")
)
def glcm_window_pallas(
    patches: jax.Array,
    *,
    levels: int,
    offsets: tuple[tuple[int, int], ...],
    copies: int = 1,
    interpret: bool = False,
    quant=None,
) -> jax.Array:
    """Per-window multi-offset GLCMs of an extracted patch grid (int32).

    ``patches`` is (gh, gw, rh, rw) → (gh, gw, n_offsets, L, L), or a
    batched (B, gh, gw, rh, rw) grid → (B, gh, gw, n_offsets, L, L). The
    kernel grid is (B, gh, gw) — one launch computes the whole texture map,
    with each window's patch DMA'd to VMEM and voted independently.

    With ``quant=(lo, span)`` the patches are RAW values, binned in-register
    per window; per-image (B,) params apply to every window of that image
    (windows share their image's quantization range).
    """
    if patches.ndim not in (4, 5):
        raise ValueError(
            f"expected (gh, gw, rh, rw) or (B, gh, gw, rh, rw) patches, "
            f"got {patches.shape}"
        )
    batched = patches.ndim == 5
    p = patches.astype(jnp.float32 if quant is not None else jnp.int32)
    if not batched:
        p = p[None]
    b, gh, gw, rh, rw = p.shape
    for dy, dx in offsets:
        if not (0 <= dy < rh) or abs(dx) >= rw:
            raise ValueError(
                f"offset (dy={dy}, dx={dx}) does not fit region ({rh}, {rw})"
            )
    n_off = len(offsets)
    lp = _padded_levels(levels)

    in_specs = [
        pl.BlockSpec((1, 1, 1, rh, rw), lambda bi, i, j: (bi, i, j, 0, 0)),
    ]
    args = [p]
    if quant is not None:
        in_specs.append(_quant_spec())
        args.append(_quant_table(quant, b))

    out = pl.pallas_call(
        functools.partial(
            _window_kernel,
            levels=levels,
            lp=lp,
            copies=copies,
            offsets=tuple(offsets),
            rh=rh,
            rw=rw,
            cw=_lane_chunk(rw, lp),
            fused_quant=quant is not None,
        ),
        grid=(b, gh, gw),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, 1, 1, n_off, lp, lp),
            lambda bi, i, j: (bi, i, j, 0, 0, 0),
        ),
        out_shape=jax.ShapeDtypeStruct((b, gh, gw, n_off, lp, lp), jnp.int32),
        interpret=interpret,
    )(*args)
    out = out[..., :levels, :levels]
    return out if batched else out[0]


# ---------------------------------------------------------------------------
# Kernel 4: depth-slab volumetric voting — grid = (B, n_slabs), halo via the
# next depth slab, R-copy privatized accumulators per slab
# ---------------------------------------------------------------------------

def _volume_kernel(
    *refs,
    levels: int,
    lp: int,
    copies: int,
    offsets: tuple[tuple[int, int, int], ...],
    slab_d: int,
    height: int,
    width: int,
    depth: int,
    cw: int,
    has_halo: bool = True,
    fused_quant: bool = False,
):
    # refs is (cur, [nxt,] [q,] o): the next-slab halo block when any offset
    # has dz > 0 (skipped otherwise — half the HBM→VMEM traffic), and the
    # (B, 2) = (lo, span) SMEM table when quantization is fused (raw f32
    # rows binned in-register; the quantized volume never exists in HBM).
    cur_ref, o_ref = refs[0], refs[-1]
    nxt_ref = refs[1] if has_halo else None
    step = pl.program_id(1)  # depth-slab step within the current volume

    @pl.when(step == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    quant = None
    if fused_quant:
        bi = pl.program_id(0)
        quant = (refs[-2][bi, 0], refs[-2][bi, 1])
    z0 = step * slab_d  # global depth of this slab's first slice

    def load(ref, z, y):
        return ref[0, z, pl.ds(y, 1), :]

    def row_fn(i, acc):
        z, y = i // height, i % height
        a = _shift_mask(_bin(load(cur_ref, z, y), levels, quant), 0,
                        z0 + z < depth, width)
        rs = []
        for dz, dy, dx in offsets:  # static unroll over directions
            # Reference row: depth z + dz (this slab or the halo slab), row
            # y + dy (dy may be NEGATIVE for the dz=+1 directions), lanes
            # rotated by dx; out-of-volume positions are dead votes.
            zz, yy = z + dz, y + dy
            yc = jnp.clip(yy, 0, height - 1)
            if dz == 0:
                raw = load(cur_ref, z, yc)
            else:
                raw = jnp.where(
                    zz < slab_d,
                    load(cur_ref, jnp.minimum(zz, slab_d - 1), yc),
                    load(nxt_ref, jnp.maximum(zz - slab_d, 0), yc),
                )
            ok = (z0 + zz < depth) & (yy >= 0) & (yy < height)
            rs.append(_shift_mask(_bin(raw, levels, quant), dx, ok, width))
        return _vote_row(a, rs, lp, cw, acc)

    acc = _accumulate(slab_d * height, copies, row_fn, len(offsets), lp)
    for k in range(len(offsets)):
        o_ref[0, k, :, :] += acc[k]


@functools.partial(
    jax.jit,
    static_argnames=("levels", "offsets", "slab_d", "copies", "interpret"),
)
def glcm_volume_pallas(
    vol: jax.Array,
    *,
    levels: int,
    offsets: tuple[tuple[int, int, int], ...],
    slab_d: int = DEFAULT_SLAB_D,
    copies: int = 1,
    interpret: bool = False,
    quant=None,
) -> jax.Array:
    """One pass over quantized volume(s) → multi-direction 3-D GLCMs (int32).

    ``vol`` is (D, H, W) → (n_offsets, L, L), or (B, D, H, W) →
    (B, n_offsets, L, L); the batch is the leading grid axis, so a whole
    stack of volumes is ONE kernel launch with the per-volume accumulator
    selected by the output ``index_map``.

    The grid is (B, n_slabs): each step DMAs one (slab_d, H, W) depth slab
    to VMEM plus the NEXT slab as the inter-slice halo (``index_map``
    clamped at the last slab; the clamp is safe because depths >= D are
    masked in-kernel), so the Pallas pipeline double-buffers the HBM→VMEM
    slab transfer against the previous slab's voting matmuls — the paper's
    two-stream timeline along the depth axis. ``offsets`` are (dz, dy, dx)
    voxel offsets with 0 <= dz <= slab_d (the halo reach); dy/dx may be
    negative (masked in-plane). ``copies`` is the paper's R: private
    (L, L) sub-accumulators per slab, summed before leaving the kernel.
    Depth is padded to a slab multiple (padded slices masked). The VMEM
    working set is 2 inputs × 2 buffers × slab_d·H·W·4B plus a few
    (L_pad, chunk) one-hots — independent of B and D, which only advance
    the DMA source (``kernels.ops.glcm_pallas_volume`` sizes ``slab_d`` so
    it fits).
    """
    if vol.ndim not in (3, 4):
        raise ValueError(
            f"expected (D, H, W) or (B, D, H, W) volume, got {vol.shape}"
        )
    batched = vol.ndim == 4
    d, h, w = vol.shape[-3:]
    for dz, dy, dx in offsets:
        if not (0 <= dz <= slab_d):
            raise ValueError(f"dz={dz} must be in [0, slab_d={slab_d}]")
        if abs(dy) >= h or abs(dx) >= w:
            raise ValueError(
                f"in-plane offset (dy={dy}, dx={dx}) exceeds plane ({h}, {w})"
            )
    vols = vol.astype(jnp.float32 if quant is not None else jnp.int32)
    if not batched:
        vols = vols[None]
    pad_d = (-d) % slab_d
    volp = jnp.pad(vols, ((0, 0), (0, pad_d), (0, 0), (0, 0)), constant_values=-1)
    b, dp, _, _ = volp.shape
    steps = dp // slab_d
    n_off = len(offsets)
    lp = _padded_levels(levels)

    in_specs = [pl.BlockSpec((1, slab_d, h, w), lambda bi, i: (bi, i, 0, 0))]
    args = [volp]
    has_halo = max((dz for dz, _, _ in offsets), default=0) > 0
    if has_halo:
        # Halo: the NEXT depth slab of the SAME volume (clamped at the
        # last slab; safe — out-of-volume depths are masked in-kernel).
        # Skipped entirely when every offset stays in-slab (dz == 0): the
        # halo block would never be read, only DMA'd.
        in_specs.append(
            pl.BlockSpec(
                (1, slab_d, h, w),
                lambda bi, i: (bi, jnp.minimum(i + 1, steps - 1), 0, 0),
            )
        )
        args.append(volp)
    if quant is not None:
        in_specs.append(_quant_spec())
        args.append(_quant_table(quant, b))

    out = pl.pallas_call(
        functools.partial(
            _volume_kernel,
            levels=levels,
            lp=lp,
            copies=copies,
            offsets=tuple(offsets),
            slab_d=slab_d,
            height=h,
            width=w,
            depth=d,
            cw=_lane_chunk(w, lp),
            has_halo=has_halo,
            fused_quant=quant is not None,
        ),
        grid=(b, steps),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, n_off, lp, lp), lambda bi, i: (bi, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, n_off, lp, lp), jnp.int32),
        interpret=interpret,
    )(*args)
    out = out[..., :levels, :levels]
    return out if batched else out[0]
