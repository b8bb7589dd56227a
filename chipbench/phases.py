#!/usr/bin/env python3
"""Where the device's idle time goes, by engine phase: the program's own
spans, read from a profiler trace beside the harness's.

While a ``jax.profiler`` capture runs, ``GLCMEngine`` marks each dispatch
with host spans named ``repro.glcm.*``: ``dispatch`` around ``pad``,
``h2d`` (the host→device copy), ``launch`` and ``readback``.
``chipbench.trace`` reads them (``load`` keeps them under ``"program"``,
``reduce`` gives ``span_ns`` and ``idle_by_label``); this tool adds the
dispatch span's self time and prints the whole breakdown of one window.

    python3 chipbench/phases.py --workload <cell> --seed <n> --seconds <s> [--trace-out <path>]

runs one cell's window traced, as ``chipbench/run.py --trace 1`` does, and
prints one JSON line: ms per request of each program span and of the
dispatch span's self time (its duration less its four phases), and the
idle seconds by label. It checks no answer; ``run.py`` does that.
"""

from __future__ import annotations

import argparse
import gc
import json
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from chipbench import data, trace  # noqa: E402
from chipbench.traffic import RealClock, loop  # noqa: E402

DISPATCH = "glcm.dispatch"
PHASES = ("glcm.pad", "glcm.h2d", "glcm.launch", "glcm.readback")


def dispatch_self_ns(spans: dict) -> float | None:
    """The dispatch spans' time outside their four phases, or None where
    the trace holds no dispatch span. Phases nest inside their dispatch,
    and clipping to the window keeps them nested."""
    if DISPATCH not in spans:
        return None
    return spans[DISPATCH] - sum(spans.get(p, 0.0) for p in PHASES)


def summarize(t: dict, requests: int) -> dict:
    """The phase breakdown of one traced window that served ``requests``."""
    reduced = trace.reduce(t)
    spans = reduced.span_ns
    self_ns = dispatch_self_ns(spans)
    out = {
        "requests": requests,
        "window_s": reduced.window_ns / 1e9,
        "device_idle_pct": 100.0 * reduced.idle_share,
        "span_ms": {k: ns / 1e6 / requests for k, ns in sorted(spans.items())},
        "idle_gaps": [[k, ns / 1e9] for k, ns in reduced.idle_by_label()],
        "harness_idle_gaps": [[k, ns / 1e9] for k, ns in reduced.gap_totals()],
    }
    if "glcm.h2d" in spans:
        out["h2d_ms"] = spans["glcm.h2d"] / 1e6 / requests
    if self_ns is not None:
        out["engine_self_ms"] = self_ns / 1e6 / requests
    return out


def trace_window(name: str, seed: int, seconds: float) -> tuple[dict, int]:
    """Set the cell up as ``run.py`` does and trace one window of its
    traffic: (the trace in plain form with the program's spans, requests
    served)."""
    import jax

    from chipbench import run as harness

    prep = harness.prepare(name, seed)
    gc.collect()
    gc.freeze()  # as run.py: set-up's objects leave the collector's scans
    log_dir = tempfile.mkdtemp(prefix="chipbench-phases-")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=options)

    def span(label):
        return jax.profiler.TraceAnnotation(trace.PREFIX + label)

    try:
        with span(trace.WINDOW):
            window = loop(prep.cell["loop"]).run(
                prep.engine, data.request_stream(prep.pool, seed), prep.cell,
                seconds, seed, RealClock, span)
    finally:
        jax.profiler.stop_trace()
        gc.unfreeze()
    loaded = trace.load(trace.find_xplane(log_dir))
    shutil.rmtree(log_dir, ignore_errors=True)
    return loaded, len(window.records)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace-out", help="write the trace, read into plain JSON, here")
    args = ap.parse_args(argv)
    from chipbench.run import HarnessError, log

    try:
        loaded, requests = trace_window(args.workload, args.seed, args.seconds)
    except (HarnessError, ImportError, FileNotFoundError) as exc:
        log(f"error: {type(exc).__name__}: {exc}")
        return 2
    if args.trace_out:
        Path(args.trace_out).write_text(json.dumps(loaded))
    print(json.dumps(summarize(loaded, requests)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
