"""Distributed GLCM — the paper's Scheme 3 generalized from "K blocks, two
CUDA streams, one GPU" to "K devices on a pod/mesh".

The input is sharded along its leading spatial axis over one or more mesh
axes — image ROWS for 2-D specs, volume DEPTH for volumetric ``ndim=3``
specs.  Each device:

  1. sends the top ``halo`` leading slices of its shard to its upper
     neighbour via ``ppermute`` — the halo of paper Eq. (8)/(9) (``Pad``
     rows) realized as a boundary exchange instead of an overlapped copy;
     ``halo`` is the offset's leading delta (dy for images, dz voxels for
     volumes — e.g. a 2-voxel exchange for a d=2 inter-slice direction);
  2. computes a *private partial GLCM* of its shard (+halo) with the
     conflict-free one-hot matmul (Scheme 2 — each device's partial matrix
     is a "copy" in the paper's sense, at mesh scale);
  3. a single ``psum`` merges the copies (the paper's final reduction).

Exactness: every pixel/voxel pair is owned by the shard holding its
*associate* element, so pairs crossing a shard boundary are counted exactly
once. The halo received by the bottom shard is a ``-1`` sentinel, whose
one-hot row is zero (vote dropped), which also handles the input's trailing
edge. In-plane deltas (dx, and dy for volumes — which may be NEGATIVE for
the dz=+1 directions) never cross shards: they are sliced inside each
shard's resident planes by ``local_partial_nd``.

Also provided: ``glcm_auto_sharded`` — the same math expressed with plain
sharding constraints, letting GSPMD insert the reduction; used to
cross-validate the explicit version and in the dry-run roofline — and
``glcm_sharded_batch``, which adds the serving dimension: a (B, H, W) /
(B, D, H, W) stack whose *batch* axis is sharded over one mesh axis while
the leading spatial axis of each input reuses the same halo-exchange
sharding over another.

Region-structured specs (``spec.region`` of "tiles"/"window") change the
decomposition: instead of sharding raw leading slices and exchanging halos,
the **window grid itself** is sharded — the region grid is extracted once
and its leading axis distributed over the mesh. Every region is wholly
owned by one device, so there is NO halo exchange and no final psum: the
output (…, *grid, L, L) texture map stays sharded along the leading grid
axis (pure map parallelism — the paper's image partitioning as the unit of
distribution rather than an intra-GLCM trick).
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.spec import GLCMSpec

__all__ = [
    "glcm_sharded",
    "glcm_sharded_batch",
    "glcm_auto_sharded",
    "local_partial_glcm",
    "local_partial_nd",
]


def _shard_plan(levels, d, theta, spec, shape):
    """Resolve the per-shard compute through the plan/backend layer.

    Legacy scalar args build a single-offset 2-D spec; an explicit ``spec``
    overrides them (and may be volumetric).  The returned plan's backend
    must declare the ``sharded_partial`` capability (its sentinel-masked
    ``local_partial`` is the per-shard kernel); "auto" resolves to a capable
    backend.  Returns (plan, levels, offset) with ``offset`` the per-axis
    (dy, dx) / (dz, dy, dx) tuple.
    """
    from repro.core.plan import compile_plan

    if spec is None:
        if levels is None or d is None or theta is None:
            raise ValueError("pass either spec= or (levels, d, theta)")
        spec = GLCMSpec(levels=levels, pairs=((d, theta),), scheme="auto")
    else:
        if levels is not None or d is not None or theta is not None:
            raise ValueError("pass either spec= or (levels, d, theta), not both")
        if spec.quantize is not None or spec.symmetric or spec.normalize:
            raise ValueError(
                "sharded GLCM expects pre-quantized images and returns raw "
                "counts; quantize/symmetric/normalize must be unset in spec"
            )
    spec.single_pair()  # sharded compute is single-offset
    plan = compile_plan(spec, shape, require=("sharded_partial",))
    return plan, plan.spec.levels, plan.spec.offsets()[0]


def _onehot(v: jax.Array, levels: int) -> jax.Array:
    iota = jax.lax.broadcasted_iota(jnp.int32, (v.shape[0], levels), 1)
    return (v[:, None] == iota).astype(jnp.int8)


def local_partial_nd(
    ext: jax.Array, levels: int, offset: tuple[int, ...], local_n: int
) -> jax.Array:
    """Partial GLCM of a leading-axis shard extended with halo slices.

    ``ext`` is (local_n + offset[0], *rest) int32 — a row shard of an image
    for 2-D offsets, a depth slab of a volume for 3-D offsets — with -1
    sentinels for out-of-input halo elements. The leading delta is realized
    by the halo; the remaining (possibly negative) deltas are sliced within
    the shard's resident planes. Votes with either side masked (-1 → zero
    one-hot row) drop.
    """
    d0 = offset[0]
    assoc = ext[:local_n]
    ref = ext[d0 : local_n + d0]
    for ax, delta in enumerate(offset[1:], start=1):
        size = ext.shape[ax]
        ix_a = [slice(None)] * assoc.ndim
        ix_r = [slice(None)] * ref.ndim
        if delta >= 0:
            ix_a[ax] = slice(0, size - delta)
            ix_r[ax] = slice(delta, size)
        else:
            ix_a[ax] = slice(-delta, size)
            ix_r[ax] = slice(0, size + delta)
        assoc = assoc[tuple(ix_a)]
        ref = ref[tuple(ix_r)]
    a = assoc.reshape(-1)
    r = ref.reshape(-1)
    A = _onehot(a, levels)
    R = _onehot(r, levels)
    return jax.lax.dot_general(
        R, A, (((0,), (0,)), ((), ())), preferred_element_type=jnp.int32
    )


def local_partial_glcm(
    ext: jax.Array, levels: int, dy: int, dx: int, local_h: int
) -> jax.Array:
    """2-D convenience form of :func:`local_partial_nd` (kept for callers
    that think in (dy, dx) scalars): partial GLCM of a row shard extended
    with ``dy`` halo rows."""
    return local_partial_nd(ext, levels, (dy, dx), local_h)


def _region_grid_partials(patches: jax.Array, local_partial, levels, offset):
    """Per-region GLCMs of a (..., *region_shape) patch block: every region
    is wholly local, so the partial of each patch (halo-free: local_n =
    r0 - offset[0]) IS its exact GLCM."""
    nd = len(offset)
    rshape = patches.shape[-nd:]
    flat = patches.reshape((-1,) + rshape).astype(jnp.int32)
    mats = jax.vmap(
        lambda p: local_partial(p, levels, offset, rshape[0] - offset[0])
    )(flat)
    return mats.reshape(patches.shape[:-nd] + (levels, levels))


def glcm_sharded(
    img: jax.Array,
    levels: int | None = None,
    d: int | None = None,
    theta: int | None = None,
    mesh: Mesh = None,
    *,
    axis: str | tuple[str, ...] = "data",
    spec: GLCMSpec | None = None,
) -> jax.Array:
    """Exact GLCM of an input sharded along its leading spatial axis over
    ``axis`` of ``mesh`` — image rows for 2-D, volume depth for ndim=3.

    The per-shard partial compute is resolved through ``compile_plan`` (the
    backend must declare ``sharded_partial``); pass ``spec=`` for the
    spec-native API (including volumetric specs over (D, H, W) volumes) or
    the legacy ``(levels, d, theta)`` scalars. Returns the full (L, L)
    int32 GLCM, replicated on every device.

    With a region-structured ``spec`` the WINDOW GRID is sharded instead of
    raw slices: the region grid is extracted and its leading axis
    distributed over ``axis`` (it must divide evenly). Regions never span
    shards, so no halo is exchanged and no psum is needed; returns the
    (*grid, L, L) int32 texture map, sharded along the leading grid axis.
    """
    if mesh is None:
        raise ValueError("glcm_sharded requires a mesh")
    axes = (axis,) if isinstance(axis, str) else tuple(axis)
    plan, levels, offset = _shard_plan(levels, d, theta, spec, img.shape)
    if img.ndim != len(offset):
        # compile_plan would accept a (B, H, W) stack as a *batched* plan;
        # here the leading axis is the SHARDING axis, so a mis-ranked input
        # must fail loudly instead of sharding the wrong dimension.
        raise ValueError(
            f"glcm_sharded shards a single {len(offset)}-D input, got shape "
            f"{img.shape}; use glcm_sharded_batch for stacks"
        )
    local_partial = plan.backend.local_partial
    n_shards = 1
    for a in axes:
        n_shards *= mesh.shape[a]
    flat_axis = axes if len(axes) > 1 else axes[0]
    if spec is not None and spec.region != "global":
        from repro.core.schemes import extract_regions

        patches = extract_regions(img, spec.region_shape, spec.strides)
        g0 = patches.shape[0]
        if g0 % n_shards:
            raise ValueError(
                f"region grid extent {g0} not divisible by {n_shards} shards"
            )
        fn = jax.shard_map(
            lambda p: _region_grid_partials(p, local_partial, levels, offset),
            mesh=mesh,
            # out: (*grid, L, L) — len(offset) grid axes + the (L, L) matrix
            in_specs=P(flat_axis, *([None] * (patches.ndim - 1))),
            out_specs=P(flat_axis, *([None] * (len(offset) + 1))),
        )
        return fn(patches)
    n0 = img.shape[0]
    rest = img.shape[1:]
    d0 = offset[0]
    if n0 % n_shards:
        raise ValueError(
            f"leading extent {n0} not divisible by {n_shards} shards"
        )
    local_n = n0 // n_shards
    if d0 > local_n:
        raise ValueError(f"halo {d0} exceeds shard extent {local_n}")

    def shard_fn(img_shard):
        # img_shard: (local_n, *rest). Send my top d0 slices to the shard
        # above me; receive my halo from the shard below. The bottom shard
        # receives nothing → fill with the -1 sentinel (trailing edge).
        idx = jax.lax.axis_index(axes)  # linearized index over the axes
        n = n_shards
        if d0 > 0:
            top = jax.lax.dynamic_slice_in_dim(img_shard, 0, d0, axis=0)
            perm = [(i, i - 1) for i in range(1, n)]
            halo = jax.lax.ppermute(top, flat_axis, perm)
            is_bottom = idx == n - 1
            halo = jnp.where(is_bottom, jnp.full_like(halo, -1), halo)
        else:
            halo = jnp.zeros((0,) + rest, img_shard.dtype)
        ext = jnp.concatenate([img_shard, halo], axis=0)
        part = local_partial(ext.astype(jnp.int32), levels, offset, local_n)
        return jax.lax.psum(part, flat_axis)

    fn = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=P(flat_axis, *([None] * (img.ndim - 1))),
        out_specs=P(None, None),
    )
    return fn(img)


def glcm_sharded_batch(
    imgs: jax.Array,
    levels: int | None = None,
    d: int | None = None,
    theta: int | None = None,
    mesh: Mesh = None,
    *,
    batch_axis: str = "data",
    row_axis: str | None = "model",
    spec: GLCMSpec | None = None,
) -> jax.Array:
    """Exact GLCMs of a (B, H, W) / (B, D, H, W) stack sharded over the mesh.

    The batch axis is sharded over ``batch_axis`` (pure data parallelism —
    the serving layout: independent requests land on independent devices)
    and, when ``row_axis`` is given, the leading spatial axis of every input
    (rows of an image, depth of a volume) is additionally sharded over
    ``row_axis`` with the same ppermute halo exchange as
    :func:`glcm_sharded` (Scheme 3's Pad region as a boundary exchange).
    ``row_axis=None`` keeps whole inputs per device.

    Returns the full (B, L, L) int32 GLCM stack; the batch axis of the
    result stays sharded over ``batch_axis``, each (L, L) slice replicated
    within its row-sharding group.

    With a region-structured ``spec`` the WINDOW GRID replaces raw slices as
    the second sharding axis: the (B, *grid) grid of regions is extracted
    and its leading grid axis sharded over ``row_axis`` (no halo exchange,
    no psum — regions are wholly device-local). Returns the (B, *grid, L, L)
    int32 texture maps, sharded over (batch_axis, row_axis).
    """
    if mesh is None:
        raise ValueError("glcm_sharded_batch requires a mesh")
    plan, levels, offset = _shard_plan(levels, d, theta, spec, imgs.shape)
    nd = len(offset)
    if imgs.ndim != nd + 1:
        raise ValueError(
            f"expected a batched {nd + 1}-D stack for an ndim={nd} spec, "
            f"got {imgs.shape}"
        )
    local_partial = plan.backend.local_partial
    b = imgs.shape[0]
    n_batch = mesh.shape[batch_axis]
    if b % n_batch:
        raise ValueError(f"batch {b} not divisible by {n_batch} shards")
    if spec is not None and spec.region != "global":
        from repro.core.schemes import extract_regions

        n_rows = mesh.shape[row_axis] if row_axis is not None else 1
        patches = extract_regions(imgs, spec.region_shape, spec.strides)
        g0 = patches.shape[1]
        if g0 % n_rows:
            raise ValueError(
                f"region grid extent {g0} not divisible by {n_rows} shards"
            )
        fn = jax.shard_map(
            lambda p: _region_grid_partials(p, local_partial, levels, offset),
            mesh=mesh,
            # out: (B, *grid, L, L) — nd grid axes + the (L, L) matrix
            in_specs=P(batch_axis, row_axis, *([None] * (patches.ndim - 2))),
            out_specs=P(batch_axis, row_axis, *([None] * (nd + 1))),
        )
        return fn(patches)
    n0 = imgs.shape[1]
    rest = imgs.shape[2:]
    d0 = offset[0]
    n_rows = mesh.shape[row_axis] if row_axis is not None else 1
    if n0 % n_rows:
        raise ValueError(
            f"leading extent {n0} not divisible by {n_rows} shards"
        )
    local_n = n0 // n_rows
    if d0 > local_n:
        raise ValueError(f"halo {d0} exceeds shard extent {local_n}")

    def shard_fn(shard):
        # shard: (B/n_batch, local_n, *rest). Leading slices travel exactly
        # as in glcm_sharded, with the batch dim riding along in the
        # ppermute.
        if row_axis is not None and d0 > 0:
            top = shard[:, :d0]
            perm = [(i, i - 1) for i in range(1, n_rows)]
            halo = jax.lax.ppermute(top, row_axis, perm)
            is_bottom = jax.lax.axis_index(row_axis) == n_rows - 1
            halo = jnp.where(is_bottom, jnp.full_like(halo, -1), halo)
        else:
            # No row sharding (or d0 == 0): the halo is the input's own
            # trailing edge — d0 sentinel slices that vote into the dead bin.
            halo = jnp.full((shard.shape[0], d0) + rest, -1, shard.dtype)
        ext = jnp.concatenate([shard, halo], axis=1).astype(jnp.int32)
        part = jax.vmap(
            lambda e: local_partial(e, levels, offset, local_n)
        )(ext)
        if row_axis is not None:
            part = jax.lax.psum(part, row_axis)
        return part

    fn = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=P(batch_axis, row_axis, *([None] * (nd - 1))),
        out_specs=P(batch_axis, None, None),
    )
    return fn(imgs)


def glcm_auto_sharded(
    img: jax.Array,
    levels: int | None = None,
    d: int | None = None,
    theta: int | None = None,
    mesh: Mesh = None,
    *,
    axis: str = "data",
    spec: GLCMSpec | None = None,
) -> jax.Array:
    """GSPMD-auto variant: express the one-hot voting matmul on the globally
    sharded input and let XLA partition the contraction (pair axis sharded →
    all-reduce of the (L, L) partials). Cross-validates ``glcm_sharded`` and
    supplies the collective schedule the roofline reads.

    The compute is resolved through the backend registry (same conflict-free
    backend the halo-exchange path uses), applied to the globally-sharded
    input so GSPMD inserts the reduction. Region-structured specs return the
    (*grid, L, L) texture map (GSPMD shards the extraction + per-region
    voting; no reduction is needed across regions)."""
    from repro.core import backends as _backends

    if mesh is None:
        raise ValueError("glcm_auto_sharded requires a mesh")
    plan, levels, offset = _shard_plan(levels, d, theta, spec, img.shape)
    if img.ndim != len(offset):
        raise ValueError(
            f"glcm_auto_sharded shards a single {len(offset)}-D input, got "
            f"shape {img.shape}"
        )
    sharded = jax.lax.with_sharding_constraint(
        img, NamedSharding(mesh, P(axis, *([None] * (img.ndim - 1))))
    )
    out = _backends.compute_regions(
        plan.backend, sharded[None].astype(jnp.int32), plan.spec
    )
    return out[0, ..., 0, :, :].astype(jnp.int32)
