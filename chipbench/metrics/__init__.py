"""Per-layer metric readers: one file per metric, named as the metric in
``BENCHMARK.json`` (``<name>.py``), each with ``read(ctx) -> float | None``.
A reader that finds nothing to read returns None and the metric is left
out of the result line. ``ctx`` is ``chipbench.run.Context``.

The helpers below are shared by the kernel readers: a kernel is found in
the trace by a regular expression on its HLO instruction name, which the
Pallas call takes from the jitted wrapper (``glcm_fused_pallas.1``), so
the reader of a new kernel is one new file that passes its own pattern.
``KERNELS`` holds the patterns of the kernels read today; ``ANY_KERNEL``
matches every Pallas GLCM kernel of the program.
"""

from __future__ import annotations

import importlib.util
from pathlib import Path

from chipbench import roofline

KERNELS = {
    "fused": r"glcm_fused_pallas(\.\d+)?$",
}
ANY_KERNEL = r"glcm_\w+_pallas(\.\d+)?$"


def reader(name: str):
    path = Path(__file__).with_name(f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"chipbench.metrics.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def kernel_ms(ctx, pattern: str) -> float | None:
    """Device ms per request served in the traced window of the kernels
    whose HLO name matches ``pattern``."""
    if ctx.trace is None or not ctx.records:
        return None
    ns = ctx.trace.matching_ns(pattern)
    return ns / 1e6 / len(ctx.records) if ns else None


def roofline_pct(ctx, kernel: str, pattern: str) -> float | None:
    """Least time of the served requests' work over the device time of the
    kernels whose HLO name matches ``pattern``, in %; notes which term
    bounds it under ``<kernel>_roofline_bound``."""
    if ctx.trace is None or not ctx.records:
        return None
    ns = ctx.trace.matching_ns(pattern)
    if not ns:
        return None
    n = len(ctx.records)
    ops, nbytes = ctx.work
    least, bound = roofline.least_time(n * ops, n * nbytes, ctx.device_kind)
    ctx.notes[f"{kernel}_roofline_bound"] = bound
    return 100.0 * least / (ns / 1e9)
