"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only table2] [--out BENCH_glcm.json]

Output: ``name,us_per_call,derived`` CSV lines on stdout (unchanged), plus a
machine-readable ``BENCH_glcm.json`` capturing every structured row
(scheme × resolution × batch timings and the derived speedup ratios) so the
perf trajectory can be compared across PRs. ``--out ''`` disables the file.
"""

import argparse
import inspect
import json
import os
import platform
import sys
import time

import jax

from benchmarks import common
from repro.compile_cache import enable_compile_cache
from repro.core.plan import plan_cache_stats

MODULES = ("table2_scheme1", "table3_scheme2", "table4_transfer",
           "fig4_async", "fig5_speedup", "moe_dispatch", "batch_throughput",
           "texture_map", "volume_throughput", "stream_throughput",
           "serve_load")


def _batch_speedups(rows: list[dict]) -> dict:
    """scheme → {B: speedup_vs_B1} from batch_throughput's structured rows."""
    out: dict = {}
    for r in rows:
        if "speedup_vs_b1" in r:
            out.setdefault(r["scheme"], {})[f"B{r['batch']}"] = round(
                r["speedup_vs_b1"], 3
            )
    return out


def _serial_speedups(rows: list[dict]) -> dict:
    """resolution → BEST accelerated-vs-serial speedup from fig5's rows
    (the headline ratio the perf gate ratchets; see benchmarks.perf_gate)."""
    best: dict = {}
    for r in rows:
        if "speedup_vs_serial" in r:
            v = round(r["speedup_vs_serial"], 2)
            best[r["size"]] = max(best.get(r["size"], 0.0), v)
    return best


def _serial_speedups_by_path(rows: list[dict]) -> dict:
    """resolution/scheme → vs-serial speedup, every accelerated path."""
    return {
        f"{r['size']}/{r['scheme']}": round(r["speedup_vs_serial"], 2)
        for r in rows
        if "speedup_vs_serial" in r
    }


def _volume_speedups(rows: list[dict]) -> dict:
    """regime/scheme → fused-3-D-plan-vs-slice-loop speedup (plus the
    2-D-equivalent voxels/sec for every volumetric row)."""
    out: dict = {}
    for r in rows:
        if "speedup_vs_slice_loop" in r:
            out[f"{r['regime']}/{r['scheme']}"] = round(
                r["speedup_vs_slice_loop"], 3
            )
        if r.get("directions") == "all13":
            out[f"{r['regime']}/{r['scheme']}/all13_voxels_per_sec"] = r[
                "voxels_per_sec"
            ]
    return out


def _stream_speedups(rows: list[dict]) -> dict:
    """window/mode → incremental-vs-full-recompute speedup from
    stream_throughput's rows (the temporal serving headline the perf gate
    ratchets)."""
    return {
        f"window{r['window']}/{r['mode']}": round(r["speedup_vs_recompute"], 3)
        for r in rows
        if "speedup_vs_recompute" in r
    }


def _texture_map_speedups(rows: list[dict]) -> dict:
    """region/scheme → region-plan-vs-patch-loop speedup (plus the
    select-subset-vs-full-14 feature ratio) from texture_map's rows."""
    out: dict = {}
    for r in rows:
        if "speedup_vs_loop" in r:
            out[f"{r['region']}/{r['scheme']}"] = round(r["speedup_vs_loop"], 3)
        if "speedup_vs_full14" in r:
            out["features_select2"] = round(r["speedup_vs_full14"], 3)
    return out


def _serve_speedups(rows: list[dict]) -> dict:
    """metric → continuous-vs-fixed serving ratio from serve_load's rows
    (p99/p50 latency at 50% load, throughput at saturation — the serving
    headline the perf gate ratchets)."""
    return {
        r["serve_metric"]: round(r["ratio"], 3)
        for r in rows
        if "serve_metric" in r
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="substring filter on module names")
    ap.add_argument("--out", default="BENCH_glcm.json",
                    help="machine-readable results path ('' disables)")
    ap.add_argument("--trace", default="",
                    help="Chrome-trace JSON path, forwarded to modules "
                         "whose run() accepts trace= (serve_load)")
    args = ap.parse_args()

    enable_compile_cache()
    common.reset_results()
    print("name,us_per_call,derived")
    modules_run: dict = {}
    for mod_name in MODULES:
        if args.only and args.only not in mod_name:
            continue
        mod = __import__(f"benchmarks.{mod_name}", fromlist=["run"])
        before = len(common.RESULTS)
        kwargs = {}
        if args.trace and "trace" in inspect.signature(mod.run).parameters:
            kwargs["trace"] = args.trace
        t0 = time.time()
        mod.run(**kwargs)
        dt = time.time() - t0
        modules_run[mod_name] = {
            "seconds": round(dt, 2),
            "rows": len(common.RESULTS) - before,
        }
        print(f"# {mod_name} done in {dt:.1f}s", file=sys.stderr)

    # The whole run shares ONE plan cache: its hit rate is the figure of
    # merit for the serving layer (every repeat shape must be a hit).
    cache = plan_cache_stats()
    print(
        f"# plan cache: {cache['hits']} hits / {cache['misses']} misses "
        f"(hit_rate={cache['hit_rate']:.3f}, evictions={cache['evictions']})",
        file=sys.stderr,
    )

    if args.out:
        payload = {
            "benchmark": "glcm",
            "unix_time": int(time.time()),
            "jax_version": jax.__version__,
            "jax_backend": jax.default_backend(),
            "machine": {
                "platform": platform.platform(),
                "machine": platform.machine(),
                "cpu_count": os.cpu_count(),
                "python": platform.python_version(),
            },
            "modules": modules_run,
            "plan_cache": {
                "hits": cache["hits"],
                "misses": cache["misses"],
                "evictions": cache["evictions"],
                "hit_rate": round(cache["hit_rate"], 4),
            },
            "speedups": {
                "batch_vs_b1": _batch_speedups(common.RESULTS),
                "vs_serial_cpu": _serial_speedups(common.RESULTS),
                "vs_serial_cpu_by_path": _serial_speedups_by_path(
                    common.RESULTS
                ),
                "texture_map_vs_loop": _texture_map_speedups(common.RESULTS),
                "volume_throughput": _volume_speedups(common.RESULTS),
                "stream_incremental_vs_recompute": _stream_speedups(
                    common.RESULTS
                ),
                "serve_continuous_vs_fixed": _serve_speedups(common.RESULTS),
            },
            "rows": common.RESULTS,
        }
        with open(args.out, "w") as f:
            json.dump(payload, f, indent=1)
            f.write("\n")
        print(f"# wrote {len(common.RESULTS)} rows to {args.out}", file=sys.stderr)


if __name__ == "__main__":
    main()
