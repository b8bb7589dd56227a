"""The scheme registry — every GLCM execution strategy behind ONE contract.

Each backend implements

    compute(img_batch, spec, quant=None) -> (B, n_pairs, L, L) counts

where ``img_batch`` is an already-quantized int32 stack — (B, H, W) for
``spec.ndim == 2``, (B, D, H, W) for volumetric ``ndim == 3`` specs — and
``spec`` is a resolved :class:`repro.core.spec.GLCMSpec` (no "auto").
With ``quant=(lo, span)`` (scalars, or per-image (B,) arrays) the stack is
instead RAW pixels the backend bins on the fly (``caps.fused_quantize``
declares support; the plan only passes ``quant`` to capable backends) — no
quantized full-size intermediate is ever materialized.  Counts may be any
exact dtype (integer or float32); the plan widens to float32.
Range derivation, symmetric/normalize post-processing and un/batching are
the *plan's* job (``core.plan.compile_plan``) — backends only count votes,
so a new strategy is one ``register()`` call, not three ``if/elif`` edits.

Capabilities declare what each strategy can do (multi-offset fusion in a
single device pass, batch carried as a kernel grid axis, TPU-targeted
compilation, sentinel-masked partials for halo-exchange sharding, native
region grids, volumetric 3-D inputs) so the "auto" resolver and the
distributed layer can pick by *capability* instead of by name.

Scheme-name dispatch lives HERE and only here: ``glcm``/``glcm_features``,
``serve.GLCMEngine``, ``core.pipeline.glcm_feature_stream`` and
``core.distributed.glcm_sharded*`` all resolve through the registry via
``compile_plan``.
"""

from __future__ import annotations

import dataclasses
from collections.abc import Callable

import jax
import jax.numpy as jnp

from repro.core.schemes import (
    extract_regions,
    glcm_blocked,
    glcm_multi,
    glcm_scatter_batch,
    glcm_windowed,
)
from repro.core.spec import GLCMSpec
from repro.kernels import ops as kops

__all__ = [
    "Backend",
    "Capabilities",
    "available_backends",
    "compute_regions",
    "get_backend",
    "register",
    "resolve_scheme",
    "serves_window_features",
    "unregister",
]


@dataclasses.dataclass(frozen=True)
class Capabilities:
    """What a backend's strategy supports (declared, not probed)."""

    multi_offset_fused: bool = False  # all offsets in ONE device pass
    batch_grid: bool = False          # batch rides a kernel grid axis (one launch)
    tpu_only: bool = False            # compiled target is TPU (interpret elsewhere)
    sharded_partial: bool = False     # supplies sentinel-masked partials for
    #                                   halo-exchange sharding (distributed.*)
    region_grid: bool = False         # native per-region path: one fused program
    #                                   over the tile/window grid (texture maps)
    volumetric: bool = False          # serves ndim=3 (D, H, W) volume specs
    volume_only: bool = False         # serves ONLY ndim=3 specs (implies
    #                                   volumetric; enforced at register())
    fused_quantize: bool = False      # accepts raw pixels + quant=(lo, span)
    #                                   and bins on the fly (no quantized
    #                                   full-size intermediate)
    host_native: bool = False         # also exposes host_fn: a plain-NumPy
    #                                   counting path the plan calls OUTSIDE
    #                                   jit (single-core CPU fast path)


@dataclasses.dataclass(frozen=True)
class Backend:
    """One registered execution strategy.

    ``validate(spec, shape)`` (optional) rejects spec/shape combinations the
    strategy cannot serve (e.g. blocked with a non-divisible height) BEFORE
    tracing.  ``local_partial(ext, levels, offset, local_n)`` (optional, for
    ``caps.sharded_partial``) computes the partial GLCM of a halo-extended
    leading-axis shard with -1 sentinels dropped — ``offset`` is the
    per-axis (dy, dx) / (dz, dy, dx) tuple and ``local_n`` the shard's
    un-extended leading extent; this is the per-shard hook the distributed
    layer consumes.  ``region_compute(img_batch, spec, quant=None)``
    (optional, for ``caps.region_grid``) serves non-global specs natively,
    returning (B, *grid, n_pairs, L, L); backends without it are served by
    the generic patch-extraction fallback in :func:`compute_regions`.
    ``host_fn(stack_np, spec, quant)`` (optional, for ``caps.host_native``)
    is a plain-NumPy counting path — (B, *spatial) ndarray in, integer
    count ndarray out, regions included — that the plan invokes outside
    jit when the input is concrete.  ``window_features(img_batch, spec,
    names, quant=None)`` (optional) returns the named features of every
    window straight from the image, (B, gh, gw, n_pairs, len(names))
    float32, with no count matrix in between; the plan takes it where
    :func:`serves_window_features` says it applies.
    """

    name: str
    compute: Callable[..., jax.Array]
    caps: Capabilities = Capabilities()
    validate: Callable[[GLCMSpec, tuple[int, ...]], None] | None = None
    local_partial: Callable[..., jax.Array] | None = None
    region_compute: Callable[..., jax.Array] | None = None
    host_fn: Callable[..., object] | None = None
    window_features: Callable[..., jax.Array] | None = None


def supports_ndim(backend: Backend, ndim: int) -> bool:
    """Whether ``backend`` can serve specs of spatial rank ``ndim``."""
    if ndim == 3:
        return backend.caps.volumetric
    return not backend.caps.volume_only


def serves_window_features(backend: Backend, spec: GLCMSpec, names) -> bool:
    """Whether ``backend`` hands back the features ``names`` of ``spec``
    directly: a symmetric stride-1 window spec over 2-D images, every name
    one the window-features kernel computes, and few enough levels for its
    unrolled level pairs. Any other spec is counted first and its features
    taken from the counts."""
    return (
        backend.window_features is not None
        and spec.region == "window"
        and spec.ndim == 2
        and spec.symmetric
        and spec.strides == (1, 1)
        and spec.levels <= kops.WINDOW_MAX_LEVELS
        and bool(names)
        and set(names) <= set(kops.WINDOW_FEATURES)
    )


def compute_regions(
    backend: Backend, img_batch: jax.Array, spec: GLCMSpec, quant=None
) -> jax.Array:
    """Region-aware dispatch: (B, *spatial) → (B, *grid, n_pairs, L, L).

    "global" specs go straight to ``backend.compute`` (grid = ()). Non-global
    specs use the backend's native ``region_compute`` when it declares
    ``caps.region_grid``; otherwise the generic fallback extracts the patch
    grid ONCE and feeds it through ``backend.compute`` as a flat
    (B·prod(grid), *region_shape) batch — every registered strategy serves
    tiled/windowed workloads (2-D and 3-D alike) unchanged.

    ``quant=(lo, span)`` (fused quantization; only for backends declaring
    ``caps.fused_quantize``) is forwarded as-is; per-image (B,) ranges are
    repeated across each image's windows for the patch fallback, so every
    window bins with its image's range.
    """
    if spec.region == "global":
        return backend.compute(img_batch, spec, quant=quant)
    if backend.caps.region_grid:
        # register() guarantees region_compute is present iff the cap is set.
        return backend.region_compute(img_batch, spec, quant=quant)
    patches = extract_regions(img_batch, spec.region_shape, spec.strides)
    nd = spec.ndim
    b = patches.shape[0]
    grid = patches.shape[1 : 1 + nd]
    flat = patches.reshape((-1,) + patches.shape[1 + nd :])
    if quant is not None:
        lo = jnp.asarray(quant[0], jnp.float32)
        span = jnp.asarray(quant[1], jnp.float32)
        if lo.ndim:
            reps = flat.shape[0] // lo.shape[0]
            quant = (jnp.repeat(lo, reps), jnp.repeat(span, reps))
    mats = backend.compute(flat, spec, quant=quant)
    return mats.reshape((b,) + grid + mats.shape[1:])


_REGISTRY: dict[str, Backend] = {}


def register(backend: Backend) -> Backend:
    """Add ``backend`` to the registry; its name becomes a scheme name."""
    if backend.name in _REGISTRY:
        raise ValueError(f"backend {backend.name!r} is already registered")
    if backend.name == "auto":
        raise ValueError('"auto" is reserved for scheme resolution')
    if backend.caps.region_grid != (backend.region_compute is not None):
        raise ValueError(
            f"backend {backend.name!r}: caps.region_grid must match the "
            "presence of region_compute"
        )
    if backend.caps.volume_only and not backend.caps.volumetric:
        raise ValueError(
            f"backend {backend.name!r}: caps.volume_only requires "
            "caps.volumetric"
        )
    if backend.caps.host_native != (backend.host_fn is not None):
        raise ValueError(
            f"backend {backend.name!r}: caps.host_native must match the "
            "presence of host_fn"
        )
    _REGISTRY[backend.name] = backend
    return backend


def unregister(name: str) -> None:
    """Remove a registered backend (test-fixture hygiene: scratch backends
    must not leak into other tests' "auto" resolution or registry sweeps)."""
    try:
        del _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown scheme {name!r}; available: {sorted(_REGISTRY)}"
        ) from None


def get_backend(name: str) -> Backend:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown scheme {name!r}; available: {sorted(_REGISTRY)}"
        ) from None


def available_backends() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def resolve_scheme(spec: GLCMSpec, *, require: tuple[str, ...] = ()) -> str:
    """Resolve ``spec.scheme`` (possibly "auto") to a registered backend name.

    "auto" picks the production path for the running jax backend: on TPU the
    Pallas kernels (the depth-slab volume kernel for ndim=3 specs, the fused
    multi-offset kernel when a 2-D spec asks for more than one offset, else
    the pair-stream voting kernel), elsewhere the conflict-free one-hot MXU
    scheme.  ``require`` names :class:`Capabilities` fields the resolved
    backend must declare — "auto" then picks the first capable backend, and
    an explicitly named scheme that lacks one raises.  Volumetric specs
    additionally require the ``volumetric`` capability (checked for named
    schemes at plan time).
    """
    if spec.scheme != "auto":
        get_backend(spec.scheme)  # existence check; capability check in plan
        return spec.scheme
    if require:
        for name in available_backends():
            backend = _REGISTRY[name]
            if not supports_ndim(backend, spec.ndim):
                continue
            if all(getattr(backend.caps, cap) for cap in require):
                return name
        raise ValueError(
            f"no registered backend has capabilities {require!r} "
            f"for an ndim={spec.ndim} spec"
        )
    if jax.default_backend() == "tpu":
        if spec.ndim == 3:
            return "pallas_volume"
        return "pallas_fused" if spec.n_pairs > 1 else "pallas"
    return "onehot"


# ---------------------------------------------------------------------------
# The seven built-in strategies
# ---------------------------------------------------------------------------


def _vote_dtype(spec: GLCMSpec):
    """spec.accum → one-hot vote dtype request (None = per-device auto)."""
    if spec.accum == "auto":
        return None
    return jnp.int8 if spec.accum == "int" else jnp.float32


def _scatter_compute(img: jax.Array, spec: GLCMSpec, quant=None) -> jax.Array:
    # One flat integer scatter per offset over the whole stack — batched
    # scatters under vmap repeat their per-image update-loop overhead B
    # times (the committed batch_vs_b1 regression); linearizing the batch
    # into the scatter index removes that.
    return glcm_scatter_batch(img, spec.levels, spec.offsets(), quant=quant)


def _onehot_compute(img: jax.Array, spec: GLCMSpec, quant=None) -> jax.Array:
    # glcm_multi amortizes the image read across offsets and batches the
    # L×L matmuls — one program per request regardless of len(pairs).
    return glcm_multi(
        img, spec.levels, offsets=spec.offsets(), copies=spec.copies,
        dtype=_vote_dtype(spec), quant=quant,
    )


def _onehot_local_partial(ext, levels, offset, local_n):
    from repro.core.distributed import local_partial_nd  # late: no cycle

    return local_partial_nd(ext, levels, offset, local_n)


def _onehot_region_compute(img: jax.Array, spec: GLCMSpec, quant=None) -> jax.Array:
    # Native fused windowed path: one extraction + batched voting matmuls
    # with the window grid as the dot_general batch axis (any rank).
    return glcm_windowed(
        img, spec.levels, spec.pairs, spec.region_shape, spec.strides,
        offsets=spec.offsets(), copies=spec.copies,
        dtype=_vote_dtype(spec), quant=quant,
    )


def _blocked_compute(img: jax.Array, spec: GLCMSpec, quant=None) -> jax.Array:
    if quant is not None:  # caps.fused_quantize is False; the plan never does this
        raise ValueError("blocked backend does not support fused quantization")
    return jnp.stack(
        [
            glcm_blocked(
                img, spec.levels, offset=off, num_blocks=spec.num_blocks,
                dtype=_vote_dtype(spec),
            )
            for off in spec.offsets()
        ],
        axis=-3,
    )


def _blocked_validate(spec: GLCMSpec, shape: tuple[int, ...]) -> None:
    n0 = shape[-spec.ndim]
    if n0 % spec.num_blocks:
        raise ValueError(
            f"image height {n0} not divisible by num_blocks={spec.num_blocks}"
            if spec.ndim == 2
            else f"volume depth {n0} not divisible by num_blocks={spec.num_blocks}"
        )
    bh = n0 // spec.num_blocks
    for (d, t), off in zip(spec.pairs, spec.offsets()):
        if off[0] > bh:
            raise ValueError(
                f"halo {off[0]} of offset (d={d}, {t}) exceeds block height {bh}"
            )


def _quant_slice(quant, i: int):
    """Per-image quant params for one element of an unrolled batch: static
    scalars pass through; per-image (B,) arrays are sliced to length-1."""
    if quant is None:
        return None
    lo = jnp.asarray(quant[0], jnp.float32)
    span = jnp.asarray(quant[1], jnp.float32)
    if lo.ndim == 0:
        return (lo, span)
    return (lo[i : i + 1], span[i : i + 1])


def _unroll_batch(compute):
    """Wrap a Pallas backend compute with the ``spec.batch_mode`` dispatch.

    "grid" (and "auto", today's default) keeps the one-launch batch-grid
    path — the TPU serving topology.  "unroll" emits one single-image kernel
    call per batch element inside the same jitted program: under CPU
    interpret mode the batched grid's per-step interpretation overhead grows
    superlinearly with the batch extent (the committed ``batch_vs_b1``
    regression: pallas B8 at 0.598×), and B independent unit-batch launches
    restore per-image parity.  The autotuner measures both and persists the
    winner per (spec, shape, device) — see ``core.autotune``.
    """

    def dispatch(img: jax.Array, spec: GLCMSpec, quant=None) -> jax.Array:
        if spec.batch_mode != "unroll" or img.shape[0] <= 1:
            return compute(img, spec, quant=quant)
        return jnp.concatenate(
            [
                compute(img[i : i + 1], spec, quant=_quant_slice(quant, i))
                for i in range(img.shape[0])
            ],
            axis=0,
        )

    return dispatch


def _pallas_compute(img: jax.Array, spec: GLCMSpec, quant=None) -> jax.Array:
    chunk = spec.chunk if spec.chunk is not None else kops.DEFAULT_CHUNK
    return jnp.stack(
        [
            kops.glcm_pallas(
                img, spec.levels, offset=off, chunk=chunk,
                copies=max(spec.copies, 1), quant=quant,
            ).astype(jnp.float32)
            for off in spec.offsets()
        ],
        axis=-3,
    )


def _pallas_fused_compute(img: jax.Array, spec: GLCMSpec, quant=None) -> jax.Array:
    return kops.glcm_pallas_multi(
        img, spec.levels, spec.pairs, tile_h=spec.tile_h, copies=spec.copies,
        quant=quant,
    ).astype(jnp.float32)


def _pallas_fused_region_compute(img: jax.Array, spec: GLCMSpec, quant=None) -> jax.Array:
    # Windowed Pallas variant: extraction in XLA, voting in one kernel launch
    # with the (B, gh, gw) window grid as the kernel grid axes. With fused
    # quantization the extracted patches stay RAW; the kernel bins each
    # window with its image's (lo, span) in-register.
    patches = extract_regions(img, spec.region_shape, spec.strides)
    return kops.glcm_pallas_windowed(
        patches, spec.levels, spec.pairs, copies=spec.copies, quant=quant,
    ).astype(jnp.float32)


def _pallas_window_features(img: jax.Array, spec: GLCMSpec, names,
                            quant=None) -> jax.Array:
    # Every window's features in one kernel launch over the raw image: no
    # extracted patches, no per-window counts in HBM.
    return kops.glcm_pallas_window_features(
        img, spec.levels, spec.pairs, spec.region_shape, tuple(names),
        quant=quant,
    )


def _pallas_volume_compute(img: jax.Array, spec: GLCMSpec, quant=None) -> jax.Array:
    return kops.glcm_pallas_volume(
        img, spec.levels, spec.pairs, slab_d=spec.slab_d, copies=spec.copies,
        quant=quant,
    ).astype(jnp.float32)


def _pallas_volume_validate(spec: GLCMSpec, shape: tuple[int, ...]) -> None:
    if spec.ndim != 3:
        raise ValueError(
            'scheme "pallas_volume" serves only ndim=3 volume specs; use '
            '"pallas"/"pallas_fused" for 2-D images'
        )


def _native_compute(img: jax.Array, spec: GLCMSpec, quant=None) -> jax.Array:
    # Registry-correct jax-context fallback for the host-native backend: a
    # pure_callback into the NumPy counting core, so scheme="native" still
    # works inside a traced program (outer jit/vmap). The plan's fast path
    # never goes through here — it calls host_fn directly, outside jit.
    from repro.core import native as _native

    out = jax.ShapeDtypeStruct(
        (img.shape[0], spec.n_pairs, spec.levels, spec.levels), jnp.float32
    )

    def cb(x, *qargs):
        import numpy as np

        q = (np.asarray(qargs[0]), np.asarray(qargs[1])) if qargs else None
        qs = _native.quantize_stack(np.asarray(x), spec, q)
        return _native.counts_pairs(qs, spec.levels, spec.offsets()).astype(
            "float32"
        )

    args = (img,) if quant is None else (img, quant[0], quant[1])
    return jax.pure_callback(cb, out, *args)


def _native_host_fn(stack, spec: GLCMSpec, quant=None):
    from repro.core import native as _native

    return _native.native_counts(stack, spec, quant)


register(
    Backend(
        name="scatter",
        compute=_scatter_compute,
        # the contention baseline: no fast-path claims — but rank-general
        caps=Capabilities(volumetric=True, fused_quantize=True),
    )
)
register(
    Backend(
        name="onehot",
        compute=_onehot_compute,
        caps=Capabilities(
            multi_offset_fused=True, sharded_partial=True, region_grid=True,
            volumetric=True, fused_quantize=True,
        ),
        local_partial=_onehot_local_partial,
        region_compute=_onehot_region_compute,
    )
)
register(
    Backend(
        name="blocked",
        compute=_blocked_compute,
        caps=Capabilities(volumetric=True),
        validate=_blocked_validate,
    )
)
register(
    Backend(
        name="native",
        compute=_native_compute,
        caps=Capabilities(
            multi_offset_fused=True, volumetric=True, fused_quantize=True,
            host_native=True,
        ),
        host_fn=_native_host_fn,
    )
)
register(
    Backend(
        name="pallas",
        compute=_unroll_batch(_pallas_compute),
        caps=Capabilities(
            batch_grid=True, tpu_only=True, volumetric=True,
            fused_quantize=True,
        ),
    )
)
register(
    Backend(
        name="pallas_fused",
        compute=_unroll_batch(_pallas_fused_compute),
        caps=Capabilities(
            multi_offset_fused=True, batch_grid=True, tpu_only=True,
            region_grid=True, fused_quantize=True,
        ),
        region_compute=_pallas_fused_region_compute,
        window_features=_pallas_window_features,
    )
)
register(
    Backend(
        name="pallas_volume",
        compute=_unroll_batch(_pallas_volume_compute),
        caps=Capabilities(
            multi_offset_fused=True, batch_grid=True, tpu_only=True,
            volumetric=True, volume_only=True, fused_quantize=True,
        ),
        validate=_pallas_volume_validate,
    )
)
