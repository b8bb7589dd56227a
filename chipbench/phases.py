#!/usr/bin/env python3
"""Where the device's idle time goes, by engine phase: the program's own
spans, read from a profiler trace beside the harness's.

While a ``jax.profiler`` capture runs, ``GLCMEngine`` marks each dispatch
with host spans named ``repro.glcm.*``: ``dispatch`` around ``pad``,
``h2d`` (the host→device copy), ``launch`` and ``readback``.
``chipbench.trace.load`` keeps only the harness's ``chipbench.*`` spans;
``load_program`` reads the program's, prefix dropped, into the plain form
under the key ``"program"``:

  {"devices": [...], "host": [...], "program": [[span, start_ns, dur_ns], ...]}

Over the harness's window:

* ``span_ns``: program span name → ns inside the window;
* ``idle_by_label``: every idle gap of ``chipbench.trace.reduce`` cut at
  program-span edges, each piece labelled by the innermost program span
  open over it, else by the gap's own harness label. With no program spans
  it equals ``Reduced.gap_totals``.

    python3 chipbench/phases.py --workload <cell> --seed <n> --seconds <s> [--trace-out <path>]

runs one cell's window traced, as ``chipbench/run.py --trace 1`` does, and
prints one JSON line: ms per request of each program span and of the
dispatch span's self time (its duration less its four phases), and the
idle seconds by label. It checks no answer; ``run.py`` does that.
"""

from __future__ import annotations

import argparse
import bisect
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

PREFIX = "repro."
DISPATCH = "glcm.dispatch"
PHASES = ("glcm.pad", "glcm.h2d", "glcm.launch", "glcm.readback")


def load_program(xplane_path: str) -> list:
    """The program's host spans (names starting ``repro.``, prefix dropped)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    return [[e.name[len(PREFIX):], e.start_ns, e.duration_ns]
            for plane in data.planes if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events
            if e.name.startswith(PREFIX)]


def _window(t: dict) -> tuple:
    (w,) = [(s, s + d) for name, s, d in t["host"] if name == trace.WINDOW]
    return w


def span_ns(t: dict) -> dict:
    """Program span name → ns inside the window ({} without program spans)."""
    t0, t1 = _window(t)
    out: dict[str, float] = {}
    for name, s, d in t.get("program", ()):
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out[name] = out.get(name, 0.0) + (b - a)
    return out


def dispatch_self_ns(spans: dict) -> float | None:
    """The dispatch spans' time outside their four phases, or None where
    the trace holds no dispatch span. Phases nest inside their dispatch,
    and clipping to the window keeps them nested."""
    if DISPATCH not in spans:
        return None
    return spans[DISPATCH] - sum(spans.get(p, 0.0) for p in PHASES)


def _innermost(program) -> list:
    """Disjoint, ordered pieces ``(start, end, name)`` of the program's
    timeline, each named by the innermost span open over it; time outside
    every span has no piece. The spans come from one thread, so they nest
    and a stack holds the open ones."""
    pieces, stack, cursor = [], [], None

    def close_until(t):
        nonlocal cursor
        while stack and stack[-1][0] <= t:
            end, name = stack.pop()
            if end > cursor:
                pieces.append((cursor, end, name))
            cursor = end

    for s, e, name in sorted(((s, s + d, n) for n, s, d in program),
                             key=lambda x: (x[0], -x[1])):
        close_until(s)
        if stack:
            if e > stack[-1][0]:
                raise ValueError(f"program span {name} at {s} is not nested "
                                 f"in {stack[-1][1]}")
            if s > cursor:
                pieces.append((cursor, s, stack[-1][1]))
        cursor = s
        stack.append((e, name))
    close_until(float("inf"))
    return pieces


def _gap_intervals(t: dict, t0: float, t1: float):
    """Every idle interval of the window, device by device, in the order of
    ``Reduced.gaps``."""
    for dev in t["devices"]:
        ivs = [(max(s, t0), min(s + d, t1)) for _, s, d in dev["ops"]]
        merged = trace._union([iv for iv in ivs if iv[1] > iv[0]])
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                yield s, e


def idle_by_label(t: dict, reduced: trace.Reduced, n: int = 10) -> list:
    """Idle ns by label, largest first: program phases where they are open
    over an idle stretch, the gap's harness label elsewhere."""
    t0, t1 = _window(t)
    pieces = _innermost(t.get("program", ()))
    starts = [p[0] for p in pieces]
    totals: dict[str, float] = {}
    gaps = list(_gap_intervals(t, t0, t1))
    assert [e - s for s, e in gaps] == [ns for _, ns in reduced.gaps]
    for (s, e), (label, _) in zip(gaps, reduced.gaps):
        covered = 0.0
        for ps, pe, name in pieces[max(bisect.bisect_right(starts, s) - 1, 0):]:
            if ps >= e:
                break
            ov = min(e, pe) - max(s, ps)
            if ov > 0:
                totals[name] = totals.get(name, 0.0) + ov
                covered += ov
        if e - s - covered > 0:
            totals[label] = totals.get(label, 0.0) + (e - s - covered)
    return sorted(totals.items(), key=lambda kv: -kv[1])[:n]


def summarize(t: dict, requests: int) -> dict:
    """The phase breakdown of one traced window that served ``requests``."""
    reduced = trace.reduce(t)
    spans = span_ns(t)
    self_ns = dispatch_self_ns(spans)
    out = {
        "requests": requests,
        "window_s": reduced.window_ns / 1e9,
        "device_idle_pct": 100.0 * reduced.idle_share,
        "span_ms": {k: ns / 1e6 / requests for k, ns in sorted(spans.items())},
        "idle_gaps": [[k, ns / 1e9] for k, ns in idle_by_label(t, reduced)],
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
    path = trace.find_xplane(log_dir)
    loaded = {**trace.load(path), "program": load_program(path)}
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
