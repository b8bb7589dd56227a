"""compile_plan — spec + input shape → ONE cached, jitted program.

This is the single execution layer every GLCM entry point goes through:

    spec  = GLCMSpec(levels=32, pairs=PAPER_PAIRS, scheme="auto")
    plan  = compile_plan(spec, imgs.shape)          # resolved, jitted, cached
    mats  = plan(imgs)                              # (B, n_pairs, L, L)

``compile_plan`` resolves "auto" against the backend registry — consulting
the :mod:`core.autotune` winner store first, so "auto" means *tuned* when a
winner for this workload has been measured (in this or any earlier process;
the store persists to a JSON sidecar) — runs the backend's capability
validation for the concrete shape, builds the full program (quantize →
backend vote counting → symmetric/normalize → optionally Haralick
features), jits it ONCE, and caches the resulting :class:`GLCMPlan` keyed
by ``(spec, shape, features, require, tuned-choice)``.  A repeated
``(spec, shape)`` therefore returns the *same* compiled callable — no
retrace, no recompile (the tuned choice is in the key, so consuming a
persisted winner hits the cache, while a NEWLY-recorded winner misses to a
fresh compile instead of serving the stale program).  The cache is a
bounded LRU (``plan_cache_limit``, default 128 plans) so a long-lived server
that sees many shapes cannot leak compiled programs; evictions show up in
``plan_cache_stats()``.

Quantization placement: for ``quantize="uniform"`` specs on backends that
declare ``caps.fused_quantize`` (all voting backends except ``blocked``),
the plan does NOT pre-quantize.  It derives each image's (lo, span) range
parameters (static floats when ``spec.vrange`` pins the range; per-image
(B,) reductions otherwise) and hands the RAW stack plus ``quant=(lo,
span)`` to the backend, which bins values where it consumes them — sliced
pair planes in the schemes, in-register tiles in the Pallas kernels.  No
quantized (B, H, W) intermediate exists in the traced program (asserted by
jaxpr inspection in ``tests/test_fusion.py``).  "equalized" quantization
(a global-histogram transform) and non-capable backends keep the legacy
pre-quantize stage.

Host-native execution: a backend declaring ``caps.host_native`` (the
``native`` NumPy-bincount backend) is dispatched OUTSIDE jit — its
counting core is plain NumPy, and wrapping it in ``pure_callback`` would
add ~1.6 ms of marshalling per call.  The plan calls ``backend.host_fn``
on the concrete ndarray and applies the (jitted) symmetric/normalize/
features tail to the small count output.  Inside a traced context (an
outer jit/vmap over the plan), the same plan transparently falls back to
the jittable ``pure_callback`` path, so composition still works.

Region-structured workloads (``spec.region`` of "tiles"/"window") generalize
the contract: counts become (B, gh, gw, n_pairs, L, L) and features
(B, gh, gw, n_pairs, n_feats), where (gh, gw) is the tile/window grid —
validated against the concrete image shape (divisibility, window fit) BEFORE
tracing, with the per-region dispatch resolved through
``backends.compute_regions`` (native fused paths or the generic
patch-extraction fallback).

``features`` may be ``True`` (all 14 Haralick features) or a tuple of
feature names — a subset skips work the selection doesn't need (notably the
O(L³) eigendecomposition of ``max_correlation_coefficient``, which dominates
texture-map feature cost).

Where the backend can hand back the requested features of a window spec
directly (``backends.serves_window_features``: a stride-1 window over 2-D
images, features all among the window-features kernel's), the plan takes
them from it — (B, gh, gw, n_pairs, n_feats) float32 with no count matrix,
float32 cast or per-window feature tail in the program — and says so in
``GLCMPlan.window_features``.  Every other spec counts first.

Volumetric specs (``spec.ndim == 3``) run the same pipeline over (D, H, W)
volumes / (B, D, H, W) stacks: the spec's rank disambiguates a 3-length
shape, offsets/regions validate against the (D, H, W) extents pre-trace,
and the backend must declare the ``volumetric`` capability ("auto" resolves
to the depth-slab Pallas kernel on TPU, the rank-general one-hot scheme
elsewhere).

Unbatched (H, W) / (D, H, W) inputs are lifted to a leading-1 stack for the
backend's ``compute`` contract and squeezed on the way out; batchedness is
part of the cache key (a different program shape), exactly like jit's own
shape specialization.
"""

from __future__ import annotations

import collections
import dataclasses
import os
import threading
import time
from collections.abc import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import backends as _backends
from repro.core.haralick import (
    FEATURE_NAMES,
    SELECTABLE_FEATURES,
    haralick_features,
)
from repro.core.quantize import (
    is_identity_quantize,
    quantize_equalized,
    quantize_uniform,
    uniform_params,
)
from repro.core.spec import GLCMSpec
from repro.core.stream_state import GLCMStreamPlan
from repro.obs import metrics as _obs_metrics
from repro.obs import trace as _obs_trace

__all__ = [
    "GLCMPlan",
    "GLCMStreamPlan",
    "compile_plan",
    "plan_cache_clear",
    "plan_cache_limit",
    "plan_cache_stats",
]


@dataclasses.dataclass(frozen=True)
class GLCMPlan:
    """A resolved, compiled GLCM program for one input shape.

    ``spec`` is fully resolved (``spec.scheme`` names a registered backend,
    never "auto").  ``grid`` is the region grid — () for "global", else
    (gh, gw) / (gd, gh, gw).  ``fn`` is the jitted program:
    (*spatial) → (*grid, n_pairs, L, L) or (B, *spatial) →
    (B, *grid, n_pairs, L, L), where ``*spatial`` is (H, W) for ndim=2
    specs and (D, H, W) for volumetric ones; with ``features`` the trailing
    (L, L) becomes the selected Haralick feature vector.
    """

    spec: GLCMSpec
    backend: _backends.Backend
    shape: tuple[int, ...]
    features: bool | tuple[str, ...]
    fn: Callable[[jax.Array], jax.Array]
    grid: tuple[int, ...] = ()
    fused_quantize: bool = False   # quantization is binned inside the count
    host_native: bool = False      # fn runs NumPy counting outside jit
    tuned: object = None           # the autotune.TunedChoice applied, if any
    window_features: bool = False  # features straight from the backend's
    #                                window-features path, no counts
    lint: tuple | None = None      # analysis.Finding tuple once linted
    #                                (empty = verified clean; None = unlinted)

    def __call__(self, img: jax.Array) -> jax.Array:
        return self.fn(img)


_DEFAULT_CACHE_LIMIT = 128
_CACHE: collections.OrderedDict = collections.OrderedDict()
_LOCK = threading.Lock()
_STATS = {"hits": 0, "misses": 0, "evictions": 0}
_LIMIT = [_DEFAULT_CACHE_LIMIT]


def plan_cache_clear() -> None:
    """Drop every cached plan (test/bench hygiene; programs recompile lazily)."""
    with _LOCK:
        _CACHE.clear()
        _STATS["hits"] = _STATS["misses"] = _STATS["evictions"] = 0


def plan_cache_limit(limit: int | None = None) -> int:
    """Get (no argument) or set the LRU bound on cached plans.

    Setting a smaller bound evicts least-recently-used plans immediately.
    The bound must be >= 1; the default is 128.
    """
    with _LOCK:
        if limit is not None:
            if limit < 1:
                raise ValueError(f"plan cache limit must be >= 1, got {limit}")
            _LIMIT[0] = int(limit)
            while len(_CACHE) > _LIMIT[0]:
                _CACHE.popitem(last=False)
                _STATS["evictions"] += 1
        return _LIMIT[0]


def plan_cache_stats() -> dict:
    """{'hits', 'misses', 'evictions', 'hit_rate', 'size', 'limit'} of the
    plan cache (counters monotonic until clear; ``hit_rate`` is
    hits / (hits + misses), 0.0 before any lookup)."""
    with _LOCK:
        lookups = _STATS["hits"] + _STATS["misses"]
        hit_rate = _STATS["hits"] / lookups if lookups else 0.0
        return {
            **_STATS, "hit_rate": hit_rate, "size": len(_CACHE),
            "limit": _LIMIT[0],
        }


def bucket_sizes(
    max_batch: int, buckets: tuple[int, ...] | None = None
) -> tuple[int, ...]:
    """The ascending launch stack sizes a batched server pre-declares.

    ``None`` → the powers of two up to ``max_batch`` plus ``max_batch``
    itself (8 → (1, 2, 4, 8); 6 → (1, 2, 4, 6)), so a partial dispatch of
    k requests pads at most k-1 slots while only O(log max_batch) program
    shapes ever compile.  An explicit tuple is validated: positive,
    strictly ascending, ending at ``max_batch``.
    """
    if max_batch < 1:
        raise ValueError(f"max_batch must be >= 1, got {max_batch}")
    if buckets is None:
        sizes = []
        b = 1
        while b < max_batch:
            sizes.append(b)
            b *= 2
        sizes.append(max_batch)
        return tuple(sizes)
    sizes = tuple(int(b) for b in buckets)
    if not sizes or any(b < 1 for b in sizes):
        raise ValueError(f"buckets must be positive, got {buckets!r}")
    if any(a >= b for a, b in zip(sizes, sizes[1:])):
        raise ValueError(f"buckets must be strictly ascending, got {buckets!r}")
    if sizes[-1] != max_batch:
        raise ValueError(
            f"buckets must end at the batch size {max_batch}, got {buckets!r}")
    return sizes


def pick_bucket(buckets: tuple[int, ...], n: int) -> int:
    """The smallest pre-declared bucket that fits ``n`` requests."""
    if n < 1:
        raise ValueError(f"need at least one request, got {n}")
    for b in buckets:
        if b >= n:
            return b
    raise ValueError(f"{n} requests exceed the largest bucket {buckets[-1]}")


def _quantizer(spec: GLCMSpec) -> Callable[[jax.Array], jax.Array] | None:
    if spec.quantize is None:
        return None
    if spec.quantize == "uniform":
        vmin, vmax = spec.vrange if spec.vrange is not None else (None, None)
        return lambda im: quantize_uniform(im, spec.levels, vmin=vmin, vmax=vmax)
    return lambda im: quantize_equalized(im, spec.levels)


def _canonical_features(features) -> bool | tuple[str, ...]:
    """Validate/canonicalize the ``features`` argument (bool or name tuple)."""
    if isinstance(features, bool):
        return features
    names = tuple(features)
    for name in names:
        if name not in SELECTABLE_FEATURES:
            raise ValueError(
                f"unknown Haralick feature {name!r}; expected names from "
                f"{SELECTABLE_FEATURES}"
            )
    if not names:
        raise ValueError("features=() selects nothing; pass False instead")
    return names


def _lint_enabled_by_env() -> bool:
    return os.environ.get("REPRO_PLAN_LINT", "").lower() in ("1", "true", "yes")


def _cache_put(key, plan):
    """Insert ``plan`` under ``key`` (first writer wins) and enforce the LRU
    bound; returns the cached instance."""
    with _LOCK:
        plan = _CACHE.setdefault(key, plan)
        _CACHE.move_to_end(key)
        _STATS["misses"] += 1
        while len(_CACHE) > _LIMIT[0]:
            _CACHE.popitem(last=False)
            _STATS["evictions"] += 1
    return plan


def _note_compile(resolved: GLCMSpec, shape, kind: str, t_build: float,
                  t_build_tr: float) -> None:
    """Record one plan-cache miss: miss counter, compile-ms histogram, and
    (tracing on) a ``plan.compile`` span."""
    ms = (time.perf_counter() - t_build) * 1e3
    reg = _obs_metrics.get_registry()
    reg.counter("repro_plan_cache_lookups_total",
                "plan-cache lookups by result", result="miss").inc()
    reg.histogram("repro_plan_compile_ms",
                  "plan build time on cache miss (ms)",
                  scheme=resolved.scheme).observe(ms)
    tr = _obs_trace.get_tracer()
    if tr.enabled:
        tr.add_span("plan.compile", t_build_tr, tr.clock(),
                    scheme=resolved.scheme, shape=str(tuple(shape)),
                    kind=kind, ms=round(ms, 3))


def _ensure_linted(plan: GLCMPlan) -> GLCMPlan:
    """Lint ``plan`` once, cache the verdict on the entry, raise on findings.

    The verdict rides the cached plan (``plan.lint``), not the cache key: a
    plan compiled without ``check`` and later requested with
    ``check="lint"`` is linted lazily on that hit, and every subsequent
    linted lookup replays the stored verdict for free.
    """
    if plan.lint is None:
        from repro.analysis import jaxpr_lint  # late: analysis imports plan

        tr = _obs_trace.get_tracer()
        t_tr = tr.clock() if tr.enabled else 0.0
        t0 = time.perf_counter()
        findings = tuple(jaxpr_lint.lint_plan(plan))
        lint_ms = (time.perf_counter() - t0) * 1e3
        _obs_metrics.get_registry().histogram(
            "repro_plan_lint_ms", "plan-contract lint time (ms)",
            scheme=plan.spec.scheme).observe(lint_ms)
        if tr.enabled:
            tr.add_span("plan.lint", t_tr, tr.clock(),
                        scheme=plan.spec.scheme, findings=len(findings),
                        ms=round(lint_ms, 3))
        object.__setattr__(plan, "lint", findings)
    if plan.lint:
        from repro.analysis import jaxpr_lint

        raise jaxpr_lint.PlanContractError(plan.lint)
    return plan


def compile_plan(
    spec: GLCMSpec,
    shape: tuple[int, ...],
    *,
    features: bool | tuple[str, ...] = False,
    require: tuple[str, ...] = (),
    check: str | None = None,
    temporal_window: int | None = None,
) -> GLCMPlan:
    """Resolve ``spec`` for input ``shape`` and return the cached GLCMPlan.

    ``shape`` is (H, W) or (B, H, W) for 2-D specs, (D, H, W) or
    (B, D, H, W) for volumetric ``spec.ndim == 3`` specs — the spec's rank
    disambiguates a 3-length shape.  ``features=True`` appends the full
    Haralick-14 stage inside the same program (one dispatch per request); a
    tuple of feature names selects a subset in the given order (skipping the
    expensive eigendecomposition when ``max_correlation_coefficient`` is not
    requested).  ``require`` names capability fields the backend must declare
    (e.g. ``("sharded_partial",)`` from the distributed layer); "auto"
    resolves to a capable backend, and an explicitly named incapable one
    raises.

    ``check="lint"`` additionally abstract-traces the compiled program and
    runs the plan-contract lint rules (:mod:`repro.analysis`) against it,
    raising :class:`repro.analysis.PlanContractError` on any finding; the
    verdict is cached on the plan entry, so repeated linted lookups cost
    nothing.  Setting ``REPRO_PLAN_LINT=1`` in the environment turns the
    check on for every ``compile_plan`` call that doesn't pass ``check``
    explicitly (``check=""`` opts a single call back out).

    ``temporal_window=w`` compiles an **incremental temporal** plan instead:
    ``shape`` is then the per-frame spatial shape (no batch axis — one plan
    per stream) and the result is a
    :class:`~repro.core.stream_state.GLCMStreamPlan` exposing
    ``init_state()`` / ``update(state, frame)`` / ``rolling(video)``.  The
    per-frame vote delta reuses this plan's fused quantize→vote path
    (Pallas kernels included) as a unit-batch partial-counts program;
    expiry subtracts the ring-buffered delta of the frame leaving the
    ``w``-frame window, and symmetric/normalize/Haralick are applied lazily
    on the accumulated signed-int32 counts — bit-exact against a full
    recompute of the window at every step.
    """
    if check is None and _lint_enabled_by_env():
        check = "lint"
    if check not in (None, "", "lint"):
        raise ValueError(f"unknown check mode {check!r}; expected 'lint'")
    shape = tuple(int(s) for s in shape)
    nd = spec.ndim
    if temporal_window is not None:
        if not isinstance(temporal_window, int) or temporal_window < 1:
            raise ValueError(
                f"temporal_window must be a positive int or None, got "
                f"{temporal_window!r}"
            )
        if len(shape) != nd:
            raise ValueError(
                f"temporal plans stream unbatched frames: expected a "
                f"{'(H, W)' if nd == 2 else '(D, H, W)'} frame shape for an "
                f"ndim={nd} spec, got {shape} (the time axis is the stream, "
                f"not a shape dimension)"
            )
    if len(shape) not in (nd, nd + 1):
        expect = ("(H, W) or (B, H, W)" if nd == 2
                  else "(D, H, W) or (B, D, H, W)")
        raise ValueError(
            f"expected a {expect} shape for an ndim={nd} spec, got {shape}"
        )
    require = tuple(require)
    features = _canonical_features(features)
    tuned = None
    if spec.scheme == "auto":
        from repro.core import autotune as _autotune  # late: plan ↔ autotune

        tuned = _autotune.lookup(spec, shape, require=require)
    # The tuned choice is part of the key: a persisted winner hits the same
    # cached plan every time, while a newly-recorded winner misses to a
    # fresh compile instead of serving the stale program.
    key = (spec, shape, features, require, tuned, temporal_window)
    with _LOCK:
        plan = _CACHE.get(key)
        if plan is not None:
            _CACHE.move_to_end(key)
            _STATS["hits"] += 1
    tracer = _obs_trace.get_tracer()
    if plan is not None:
        _obs_metrics.get_registry().counter(
            "repro_plan_cache_lookups_total", "plan-cache lookups by result",
            result="hit").inc()
        if tracer.enabled:
            tracer.event("plan.cache_hit", scheme=plan.spec.scheme,
                         shape=str(shape))
        return _ensure_linted(plan) if check == "lint" else plan

    # Cache miss: time the plan build (backend resolution + validation +
    # program construction + jit wrapping — XLA compilation itself is lazy,
    # on first execution) for the compile span/histogram.
    t_build_tr = tracer.clock() if tracer.enabled else 0.0
    t_build = time.perf_counter()

    if tuned is not None:
        name = tuned.backend
    else:
        name = _backends.resolve_scheme(spec, require=require)
    backend = _backends.get_backend(name)
    if not _backends.supports_ndim(backend, nd):
        raise ValueError(
            f"scheme {name!r} lacks required capability 'volumetric' "
            f"(cannot serve ndim={nd} specs)"
            if nd == 3
            else f"scheme {name!r} serves only ndim=3 volume specs"
        )
    for cap in require:
        if not getattr(backend.caps, cap):
            raise ValueError(
                f"scheme {name!r} lacks required capability {cap!r}"
            )
    if tuned is not None:
        resolved = tuned.apply(spec)
    else:
        resolved = spec if spec.scheme == name else spec.replace(scheme=name)

    spatial = shape[-nd:]
    # Region validation happens against the concrete input shape BEFORE any
    # tracing: tile divisibility / window fit...
    grid = resolved.region_grid(*spatial)
    if grid:
        # ...and the backend sees patches, never the whole input, so its own
        # shape validation runs on the per-region shape it will serve.
        n_regions = 1
        for g in grid:
            n_regions *= g
        if len(shape) == nd + 1:
            n_regions *= shape[0]
        backend_shape: tuple[int, ...] = (n_regions,) + resolved.region_shape
    else:
        # Spec offsets are validated against the region for non-global specs
        # (at spec construction); for "global" the region IS the input. The
        # leading spatial delta is non-negative by construction; the rest
        # may be negative (3-D inter-slice directions).
        for (d, t), off in zip(resolved.pairs, resolved.offsets()):
            if off[0] >= spatial[0] or any(
                abs(o) >= s for o, s in zip(off[1:], spatial[1:])
            ):
                raise ValueError(
                    f"offset (d={d}, {t}) → {off} exceeds "
                    f"input shape {spatial}"
                )
        backend_shape = shape
    if backend.validate is not None:
        backend.validate(resolved, backend_shape)

    quant = _quantizer(resolved)
    batched = len(shape) == nd + 1
    select = None if isinstance(features, bool) else features
    # Fused quantization: uniform binning folds into the count (the backend
    # bins sliced planes / in-register tiles); "equalized" (a global-
    # histogram transform) and non-capable backends pre-quantize as before.
    fused = resolved.quantize == "uniform" and backend.caps.fused_quantize
    vmin, vmax = resolved.vrange if resolved.vrange is not None else (None, None)

    def tail(mats: jax.Array) -> jax.Array:
        if resolved.symmetric:
            mats = mats + jnp.swapaxes(mats, -1, -2)
        if resolved.normalize:
            mats = mats / jnp.maximum(mats.sum(axis=(-2, -1), keepdims=True), 1.0)
        if features:
            mats = haralick_features(mats, select=select)
        return mats

    if temporal_window is not None:
        # Incremental temporal mode: the per-frame vote delta is this very
        # plan's quantize→vote path applied to a unit batch — the per-frame
        # partial-counts contract every backend (Pallas kernels included)
        # already serves.  Counts round-trip through int32: backend float32
        # outputs are integral (exact below 2³¹ per cell), and the rolling
        # state MUST be signed — expiry subtraction transiently underflows
        # unsigned widths (the stream-signed-accum contract).
        def delta_fn(frame: jax.Array) -> jax.Array:
            stack = frame[None]
            if fused:
                if is_identity_quantize(frame.dtype, resolved.levels,
                                        vmin, vmax):
                    stack = stack.astype(jnp.int32)
                    qargs = None
                else:
                    qargs = uniform_params(stack, vmin=vmin, vmax=vmax,
                                           batched=True)
            else:
                if quant is not None:
                    frame = quant(frame)
                stack = frame.astype(jnp.int32)[None]
                qargs = None
            counts = _backends.compute_regions(
                backend, stack, resolved, quant=qargs
            )
            return counts[0].astype(jnp.int32)

        plan = GLCMStreamPlan(
            spec=resolved, backend=backend, shape=shape,
            window=temporal_window, features=features, delta_fn=delta_fn,
            tail_fn=tail, grid=grid, fused_quantize=fused,
            host_native=backend.caps.host_native, tuned=tuned,
        )
        _note_compile(resolved, shape, "stream", t_build, t_build_tr)
        plan = _cache_put(key, plan)
        return _ensure_linted(plan) if check == "lint" else plan

    names = FEATURE_NAMES if features is True else features
    direct = bool(features) and _backends.serves_window_features(
        backend, resolved, names)

    def run(img: jax.Array) -> jax.Array:
        if fused:
            stack = img if batched else img[None]
            if is_identity_quantize(img.dtype, resolved.levels, vmin, vmax):
                # Provably-identity quantization (uint8, levels=256, vrange
                # (0, 255)): the input already holds the level indices, so
                # the fused affine would be pure wasted arithmetic.  Hand
                # the backend a plain cast with no quant params — the
                # traced program stays free of binning floor/div ops
                # (asserted by the identity-quantize-float-free lint rule).
                stack = stack.astype(jnp.int32)
                qargs = None
            else:
                # The backend sees RAW pixels plus per-image (lo, span); no
                # quantized full-size intermediate exists in this program.
                qargs = uniform_params(stack, vmin=vmin, vmax=vmax, batched=True)
        else:
            if quant is not None:
                # Per-image quantization: each image of a batch uses its OWN
                # value range (identical to quantizing one image at a time).
                # Regions share their image's quantization — one gray-level
                # mapping per texture map, never per window.
                img = jax.vmap(quant)(img) if batched else quant(img)
            img = img.astype(jnp.int32)
            stack = img if batched else img[None]
            qargs = None
        if direct:
            out = backend.window_features(stack, resolved, names, quant=qargs)
            return out if batched else out[0]
        mats = _backends.compute_regions(
            backend, stack, resolved, quant=qargs
        ).astype(jnp.float32)
        mats = tail(mats)
        return mats if batched else mats[0]

    host = backend.caps.host_native
    if host:
        # NumPy counting outside jit; only the small symmetric/normalize/
        # features tail is a jitted program.
        from repro.core import native as _native

        needs_tail = bool(resolved.symmetric or resolved.normalize or features)
        tail_j = jax.jit(tail) if needs_tail else None
        jit_run = jax.jit(run)  # traced-context fallback (pure_callback)

        def run_host(img):
            if isinstance(img, jax.core.Tracer):
                return jit_run(img)
            x = np.asarray(img)
            if fused:
                stack = x if batched else x[None]
                if is_identity_quantize(x.dtype, resolved.levels, vmin, vmax):
                    qargs = None  # identity: values already ARE the levels
                else:
                    qargs = _native.uniform_params_np(stack, vmin, vmax)
            else:
                if quant is not None:
                    arr = jnp.asarray(x)
                    arr = jax.vmap(quant)(arr) if batched else quant(arr)
                    x = np.asarray(arr)
                stack = x if batched else x[None]
                qargs = None
            counts = backend.host_fn(stack, resolved, qargs)
            mats = jnp.asarray(np.asarray(counts, np.float32))
            if tail_j is not None:
                mats = tail_j(mats)
            return mats if batched else mats[0]

        fn = run_host
    else:
        fn = jax.jit(run)

    plan = GLCMPlan(
        spec=resolved, backend=backend, shape=shape, features=features,
        fn=fn, grid=grid, fused_quantize=fused, host_native=host,
        tuned=tuned, window_features=direct,
    )
    _note_compile(resolved, shape, "plan", t_build, t_build_tr)
    plan = _cache_put(key, plan)
    return _ensure_linted(plan) if check == "lint" else plan
