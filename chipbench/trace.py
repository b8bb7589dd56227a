"""Profiler trace → device busy time, per-op device time and labelled idle
gaps.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a small
plain form (which is also what the test fixture holds):

  {"devices": [{"name": "/device:TPU:0", "ops": [[op, start_ns, dur_ns], ...]}],
   "host": [[span, start_ns, dur_ns], ...]}

``ops`` are the events of each device plane's "XLA Ops" line, named by
their HLO instruction (``glcm_fused_pallas.1``, ``fusion.3``, ...); ``host``
holds the harness's own ``jax.profiler.TraceAnnotation`` spans (names
starting with ``chipbench.``, prefix dropped). Host and device events share
the profiler's clock.

``reduce`` takes the window from the harness's ``window`` span, clips every
op to it, and per device forms the union of op intervals: busy time.
Every stretch of the window outside that union is an idle gap, labelled by
the host span that overlaps it most ("other" where none does).
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

PREFIX = "chipbench."
WINDOW = "window"


def _short(name: str) -> str:
    return name.split(" = ", 1)[0].lstrip("%")


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, found {paths}")
    return paths[0]


def load(xplane_path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = [[_short(e.name), e.start_ns, e.duration_ns]
                   for line in plane.lines if line.name == "XLA Ops"
                   for e in line.events]
            devices.append({"name": plane.name, "ops": ops})
        elif plane.name == "/host:CPU":
            host.extend([e.name[len(PREFIX):], e.start_ns, e.duration_ns]
                        for line in plane.lines for e in line.events
                        if e.name.startswith(PREFIX))
    return {"devices": devices, "host": host}


@dataclasses.dataclass
class Reduced:
    window_ns: float
    busy_ns: float                 # mean over devices
    op_ns: dict                    # op name → device ns in the window (all devices)
    gaps: list                     # [(label, ns)] every idle gap, all devices
    n_devices: int

    def matching_ns(self, pattern: str) -> float:
        rx = re.compile(pattern)
        return sum(ns for name, ns in self.op_ns.items() if rx.match(name))

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_ns / self.window_ns

    def top_ops(self, n: int = 10) -> list:
        return sorted(self.op_ns.items(), key=lambda kv: -kv[1])[:n]

    def gap_totals(self, n: int = 10) -> list:
        totals: dict[str, float] = {}
        for label, ns in self.gaps:
            totals[label] = totals.get(label, 0.0) + ns
        return sorted(totals.items(), key=lambda kv: -kv[1])[:n]


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _label(s: float, e: float, spans, starts) -> str:
    """The span overlapping [s, e) most. The harness's spans come from one
    thread one after another, so only the last one to start before s can
    reach into the gap from the left."""
    best, best_ns = "other", 0.0
    for name, hs, he in spans[max(bisect.bisect_right(starts, s) - 1, 0):]:
        if hs >= e:
            break
        ov = min(e, he) - max(s, hs)
        if ov > best_ns:
            best, best_ns = name, ov
    return best


def reduce(trace: dict) -> Reduced:
    windows = [(s, s + d) for name, s, d in trace["host"] if name == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {PREFIX}{WINDOW} span, found {len(windows)}")
    t0, t1 = windows[0]
    spans = sorted(((name, s, s + d) for name, s, d in trace["host"] if name != WINDOW),
                   key=lambda x: x[1])
    starts = [hs for _, hs, _ in spans]
    op_ns: dict[str, float] = {}
    busy, gaps = [], []
    for dev in trace["devices"]:
        ivs = []
        for name, s, d in dev["ops"]:
            a, b = max(s, t0), min(s + d, t1)
            if b > a:
                ivs.append((a, b))
                op_ns[name] = op_ns.get(name, 0.0) + (b - a)
        merged = _union(ivs)
        busy.append(sum(e - s for s, e in merged))
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((_label(s, e, spans, starts), e - s))
    if not busy:
        raise ValueError("the trace holds no device plane")
    return Reduced(window_ns=t1 - t0, busy_ns=sum(busy) / len(busy), op_ns=op_ns,
                   gaps=gaps, n_devices=len(busy))
