#!/usr/bin/env python3
"""Smoke test of the GLCM serving path on a TPU, compiled with Mosaic.

    python chip_smoke.py [--seed N]              # one chip: engine phases
    python chip_smoke.py --chips 4 [--seed N]    # four chips: sharded path only

One chip drives ``serve.GLCMEngine`` (``register`` / ``warmup`` / ``submit`` /
``result``, and ``open_stream`` / ``push``) at deployment sizes:

  paper_1024   the paper's Table III set: L=32, pairs (1,0°) (1,45°) (4,0°)
               (4,45°), uniform quantization, Haralick features; a batch of
               eight 1024² images                          → pallas_fused
  paper_16384  the same spec on one 16384² image: ~2.7·10⁸ pairs per offset,
               past float32's 2²⁴ integer limit            → pallas_fused
  ct_volume    a 128×512×512 CT-like volume, all 13 3-D directions
                                                           → pallas_volume
  texture_map  a 4096² image in 64² tiles                  → pallas_fused
                                                             (window kernel)
  stream       twelve 1920×1080 frames pushed into a temporal_window=8
               session                                     → pallas_fused

Every phase asserts the plan resolved to the expected Pallas backend (never
the host ``native`` path) and that Pallas is not in interpret mode, compares
the counts cell for cell with the host NumPy reference (``core.native``
quantize + ``np.bincount``, one offset at a time), and compares the Haralick
features with the same formulas evaluated in float64 on the host CPU within
``FEATURE_RTOL`` / ``FEATURE_ATOL``. It prints one line per phase.

``--chips 4`` runs only ``core.distributed`` on a 4-device mesh — a 16384²
image row-sharded with ``ppermute`` halos (``glcm_sharded``) and a batch
sharded over ``data`` (``glcm_sharded_batch``) — and compares both with the
single-device plan and the host reference.

Inputs are generated from ``--seed`` by ``repro.data.images``. All work runs
in this one process. The last line of stdout is the JSON result; any failure
exits non-zero without printing it, and so does a run that finds no TPU.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

PAPER_PAIRS = ((1, 0), (1, 45), (4, 0), (4, 45))
LEVELS = 32
# Haralick features on the chip (float32) vs the same formulas evaluated in
# float64 on the host CPU from the reference counts:
# |got - want| <= FEATURE_ATOL + FEATURE_RTOL * |want| (float32 rounding,
# reduction order, the TPU's log/exp and its eigvalsh; counts must match
# exactly).
# info_correlation_2 and max_correlation_coefficient are square roots of
# quantities that vanish on uncorrelated textures, where the root turns a
# float32 rounding error e of the radicand into sqrt(e); those two are
# compared squared, i.e. as the radicand the f32 arithmetic produced.
FEATURE_RTOL = 1e-3
FEATURE_ATOL = 1e-4
SQUARED_FEATURES = ("info_correlation_2", "max_correlation_coefficient")


class SmokeError(RuntimeError):
    pass


def _require(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeError(msg)


def host_counts(stack, spec):
    """Host NumPy reference counts of a raw (B, *spatial) stack: the same
    f32 uniform binning as the kernels, then one bincount per offset."""
    import numpy as np

    from repro.core import native

    vmin, vmax = spec.vrange if spec.vrange is not None else (None, None)
    quant = native.uniform_params_np(stack, vmin, vmax) if spec.quantize else None
    return native.native_counts(np.asarray(stack), spec, quant)


class Checker:
    """Exact count and toleranced feature comparisons for one phase."""

    def __init__(self):
        import jax

        from repro.core.haralick import FEATURE_NAMES, haralick_features

        self._cpu = jax.devices("cpu")[0]
        self._features = jax.jit(haralick_features)
        self._squared = [FEATURE_NAMES.index(n) for n in SQUARED_FEATURES]
        self.count_mismatches = 0
        self.feature_mismatches = 0
        self.feature_max_err = 0.0

    def counts(self, got, want) -> None:
        import numpy as np

        got = np.asarray(got)
        _require(got.shape == want.shape, f"count shape {got.shape} != {want.shape}")
        _require(bool(np.all(np.isfinite(got))), "non-finite counts")
        self.count_mismatches += int(np.count_nonzero(got.astype(np.int64) != want))

    def features(self, got, want_counts) -> None:
        import jax
        import numpy as np

        with jax.enable_x64(True):
            want = np.array(self._features(
                jax.device_put(want_counts.astype(np.float64), self._cpu)))
        got = np.asarray(got, np.float64)
        _require(got.shape == want.shape, f"feature shape {got.shape} != {want.shape}")
        _require(bool(np.all(np.isfinite(got))), "non-finite features")
        got[..., self._squared] **= 2
        want[..., self._squared] **= 2
        err = np.abs(got - want)
        self.feature_max_err = max(self.feature_max_err, float(err.max()))
        bad = err > FEATURE_ATOL + FEATURE_RTOL * np.abs(want)
        self.feature_mismatches += int(np.count_nonzero(bad))


def check_plan(plan, expect: str) -> str:
    from repro.kernels.ops import should_interpret

    name = plan.spec.scheme
    _require(name == expect, f"plan resolved to {name!r}, expected {expect!r}")
    _require(not plan.backend.caps.host_native, f"{name!r} is a host backend")
    _require(not should_interpret(), "Pallas would run in interpret mode")
    return name


def report(phase, backend, compile_s, first_s, steady_s, chk: Checker) -> None:
    mismatches = chk.count_mismatches + chk.feature_mismatches
    print(
        f"phase={phase} backend={backend} compile_s={compile_s:.3f} "
        f"first_s={first_s:.4f} steady_s={steady_s:.4f} "
        f"mismatches={mismatches} count_mismatches={chk.count_mismatches} "
        f"feature_mismatches={chk.feature_mismatches} "
        f"feature_max_abs_err={chk.feature_max_err:.3g}",
        flush=True,
    )
    _require(mismatches == 0, f"phase {phase}: {mismatches} mismatches")


def serve_phase(name, spec, shape, batches, expect, *, batch_size) -> None:
    """Serve ``batches`` (lists of raw requests) through a GLCMEngine:
    features on workload 0 (timed), raw counts on a second workload."""
    import numpy as np

    from repro.core.plan import compile_plan
    from repro.serve.engine import GLCMEngine, GLCMServeConfig

    eng = GLCMEngine(GLCMServeConfig(
        spec=spec, image_shape=shape, batch_size=batch_size,
        buckets=(batch_size,), features=True,
    ))
    counts_wid = eng.register(spec, shape, features=False, name="counts")
    backend = check_plan(eng.plan, expect)
    check_plan(compile_plan(spec, (batch_size, *shape)), expect)
    dtype = batches[0][0].dtype

    t0 = time.perf_counter()
    eng.warmup(dtype=dtype)
    compile_s = time.perf_counter() - t0

    chk = Checker()
    times = []
    for imgs in batches:
        t0 = time.perf_counter()
        tickets = [eng.submit(im) for im in imgs]
        feats = [eng.result(t) for t in tickets]
        times.append(time.perf_counter() - t0)
        counts = eng.map(imgs, workload=counts_wid)
        want = host_counts(np.stack(imgs), spec)
        chk.counts(counts, want)
        chk.features(np.stack(feats), want)
    report(name, backend, compile_s, times[0], statistics.median(times[1:]), chk)


def stream_phase(spec, frames, window: int) -> None:
    import numpy as np

    from repro.serve.engine import GLCMEngine, GLCMServeConfig

    eng = GLCMEngine(GLCMServeConfig(
        spec=spec, image_shape=frames[0].shape, batch_size=1, buckets=(1,),
        features=True, temporal_window=window,
    ))
    backend = check_plan(eng.stream_plan, "pallas_fused")

    t0 = time.perf_counter()
    sid = eng.open_stream()
    eng.push(sid, frames[0])
    eng.close_stream(sid)
    compile_s = time.perf_counter() - t0

    chk = Checker()
    per_frame = [host_counts(f[None], spec)[0] for f in frames]
    times = []
    sid = eng.open_stream()
    for t, frame in enumerate(frames):
        t0 = time.perf_counter()
        feats = eng.push(sid, frame)
        times.append(time.perf_counter() - t0)
        want = np.sum(per_frame[max(0, t + 1 - window) : t + 1], axis=0)
        chk.features(feats, want)
    state = eng.close_stream(sid)
    chk.counts(state.counts, want)
    report("stream", backend, compile_s, times[0], statistics.median(times[1:]), chk)


def one_chip(seed: int) -> None:
    import numpy as np

    from repro.core.spec import GLCMSpec
    from repro.data import images

    paper = GLCMSpec(levels=LEVELS, pairs=PAPER_PAIRS, quantize="uniform")

    def bucket(i):
        gen = images.smooth_texture if i % 2 else images.random_texture
        return [gen(1024, seed=seed + 8 * i + k) for k in range(8)]

    serve_phase("paper_1024", paper, (1024, 1024), [bucket(i) for i in range(3)],
                "pallas_fused", batch_size=8)

    # iid texture: every cell stays below 2²⁴, so the plan's float32 count
    # output is exact while each offset's total (~2.7·10⁸) is far past it.
    big = images.random_texture(16384, seed=seed + 100)
    serve_phase("paper_16384", paper, big.shape, [[big], [big]], "pallas_fused",
                batch_size=1)
    del big

    ct = GLCMSpec(levels=LEVELS, pairs=tuple((1, k) for k in range(13)),
                  quantize="uniform", ndim=3)
    vols = [[images.smooth_volume((128, 512, 512), seed=seed + 200 + i)]
            for i in range(2)]
    serve_phase("ct_volume", ct, vols[0][0].shape, vols, "pallas_volume",
                batch_size=1)
    del vols

    tiles = GLCMSpec(levels=LEVELS, pairs=PAPER_PAIRS, quantize="uniform",
                     region="tiles", region_shape=(64, 64))
    scenes = [[images.smooth_texture(4096, seed=seed + 300 + i)] for i in range(2)]
    serve_phase("texture_map", tiles, (4096, 4096), scenes, "pallas_fused",
                batch_size=1)
    del scenes

    base = images.smooth_texture(1920, seed=seed + 400)[:1080]
    frames = [np.roll(base, 3 * t, axis=1) for t in range(12)]
    stream_phase(paper, frames, window=8)


def four_chips(seed: int) -> None:
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core import native
    from repro.core.distributed import glcm_sharded, glcm_sharded_batch
    from repro.core.plan import compile_plan
    from repro.core.spec import GLCMSpec
    from repro.data import images

    devs = jax.devices()
    _require(len(devs) >= 4, f"--chips 4 needs 4 devices, found {len(devs)}")
    mesh = Mesh(np.array(devs[:4]), ("data",))
    rows = NamedSharding(mesh, P("data", None))
    batch = NamedSharding(mesh, P("data", None, None))

    def quantized(stack):
        spec = GLCMSpec(levels=LEVELS, quantize="uniform")
        q = native.quantize_stack(stack, spec, native.uniform_params_np(stack))
        return q.astype(np.int32)

    img = quantized(images.random_texture(16384, seed=seed + 500)[None])[0]
    stack = quantized(np.stack(
        [images.smooth_texture(1024, seed=seed + 600 + k) for k in range(8)]))
    x_rows = jax.device_put(img, rows)
    x_batch = jax.device_put(stack, batch)
    x_one = jax.device_put(img, devs[0])
    x_stack_one = jax.device_put(stack, devs[0])

    def timed(fn, x):
        t0 = time.perf_counter()
        compiled = jax.jit(fn).lower(x).compile()
        compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        out = jax.block_until_ready(compiled(x))
        first_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        jax.block_until_ready(compiled(x))
        return np.asarray(out), compile_s, first_s, time.perf_counter() - t0

    for d, theta in PAPER_PAIRS:
        spec = GLCMSpec(levels=LEVELS, pairs=((d, theta),))
        ref = native.counts_pairs(img[None], LEVELS, spec.offsets())[0, 0]
        ref_b = native.counts_pairs(stack, LEVELS, spec.offsets())[:, 0]
        cases = (
            ("sharded_rows_16384",
             lambda x, s=spec: glcm_sharded(x, mesh=mesh, spec=s),
             x_rows, x_one, ref),
            ("sharded_batch_8x1024",
             lambda x, s=spec: glcm_sharded_batch(
                 x, mesh=mesh, spec=s, batch_axis="data", row_axis=None),
             x_batch, x_stack_one, ref_b),
        )
        for name, fn, x, x1, want in cases:
            # The single-device comparison: the same spec's plan on chip 0.
            single = compile_plan(spec, x1.shape)
            check_plan(single, "pallas")
            chk = Checker()
            out, compile_s, first_s, steady_s = timed(fn, x)
            chk.counts(out, want)
            chk.counts(np.asarray(single(x1))[..., 0, :, :], want)
            report(f"{name}_d{d}_t{theta}", "sharded", compile_s, first_s,
                   steady_s, chk)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (jax found {dev.platform!r}); refusing to "
              f"run the kernels in interpret mode", file=sys.stderr)
        return 1
    if args.chips == 4:
        four_chips(args.seed)
    else:
        one_chip(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
