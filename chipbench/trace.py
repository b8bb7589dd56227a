"""Profiler trace → device busy time, per-op device time, the program's
span time and labelled idle gaps.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a small
plain form (which is also what the test fixtures hold):

  {"devices": [{"name": "/device:TPU:0", "ops": [[op, start_ns, dur_ns], ...]}],
   "host": [[span, start_ns, dur_ns], ...],
   "program": [[span, start_ns, dur_ns], ...]}

``ops`` are the events of each device plane's "XLA Ops" line, named by
their HLO instruction (``glcm_fused_pallas.1``, ``fusion.3``, ...); ``host``
holds the harness's own ``jax.profiler.TraceAnnotation`` spans (names
starting with ``chipbench.``, prefix dropped); ``program`` the program's
(``repro.``, prefix dropped: the engine's ``glcm.dispatch`` around
``glcm.pad``, ``glcm.h2d``, ``glcm.launch`` and ``glcm.readback``). Host
and device events share the profiler's clock. A trace without
``program`` reads as one in which the program wrote no span.

``reduce`` takes the window from the harness's ``window`` span, clips every
op to it, and per device forms the union of op intervals: busy time.
Every stretch of the window outside that union is an idle gap, labelled by
the host span that overlaps it most ("other" where none does). Over the
same window it adds:

* ``span_ns``: program span name → ns inside the window;
* ``idle_by_label``: every idle gap cut at program-span edges, each piece
  labelled by the innermost program span open over it, else by the gap's
  own harness label. With no program spans it equals ``gap_totals``.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

PREFIX = "chipbench."
PROGRAM_PREFIX = "repro."
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
    devices, host, program = [], [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = [[_short(e.name), e.start_ns, e.duration_ns]
                   for line in plane.lines if line.name == "XLA Ops"
                   for e in line.events]
            devices.append({"name": plane.name, "ops": ops})
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    for prefix, into in ((PREFIX, host), (PROGRAM_PREFIX, program)):
                        if e.name.startswith(prefix):
                            into.append([e.name[len(prefix):], e.start_ns, e.duration_ns])
    return {"devices": devices, "host": host, "program": program}


@dataclasses.dataclass
class Reduced:
    window_ns: float
    busy_ns: float                 # mean over devices
    op_ns: dict                    # op name → device ns in the window (all devices)
    gaps: list                     # [(label, ns)] every idle gap, all devices
    n_devices: int
    span_ns: dict = dataclasses.field(default_factory=dict)   # program span → ns
    idle_ns: dict = dataclasses.field(default_factory=dict)   # idle_by_label's totals

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

    def idle_by_label(self, n: int = 10) -> list:
        """Idle ns by label, largest first: program spans where they are
        open over an idle stretch, the gap's harness label elsewhere."""
        return sorted(self.idle_ns.items(), key=lambda kv: -kv[1])[:n]


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


def _window(trace: dict) -> tuple:
    windows = [(s, s + d) for name, s, d in trace["host"] if name == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {PREFIX}{WINDOW} span, found {len(windows)}")
    return windows[0]


def span_ns(trace: dict) -> dict:
    """Program span name → ns inside the window ({} without program spans)."""
    t0, t1 = _window(trace)
    out: dict[str, float] = {}
    for name, s, d in trace.get("program", ()):
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out[name] = out.get(name, 0.0) + (b - a)
    return out


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


def _cut(s: float, e: float, label: str, pieces, starts, totals: dict) -> None:
    """Add the idle gap [s, e) to ``totals``: each overlap with a program
    piece to that piece's span, the rest to the gap's harness label."""
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


def reduce(trace: dict) -> Reduced:
    t0, t1 = _window(trace)
    spans = sorted(((name, s, s + d) for name, s, d in trace["host"] if name != WINDOW),
                   key=lambda x: x[1])
    starts = [hs for _, hs, _ in spans]
    pieces = _innermost(trace.get("program", ()))
    piece_starts = [p[0] for p in pieces]
    op_ns: dict[str, float] = {}
    busy, gaps, idle_ns = [], [], {}
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
                label = _label(s, e, spans, starts)
                gaps.append((label, e - s))
                _cut(s, e, label, pieces, piece_starts, idle_ns)
    if not busy:
        raise ValueError("the trace holds no device plane")
    return Reduced(window_ns=t1 - t0, busy_ns=sum(busy) / len(busy), op_ns=op_ns,
                   gaps=gaps, n_devices=len(busy), span_ns=span_ns(trace),
                   idle_ns=idle_ns)
