"""Serving under load — continuous (deadline) batching vs. the old
full-batch-only engine on a bursty mixed-spec trace.

The paper's 50× is a throughput number; a serving engine also answers for
LATENCY. The old ``GLCMEngine`` only launched full ``batch_size`` stacks,
so at partial load a request waits for enough *later* arrivals of its own
workload to fill a batch — tail latency is set by traffic, not compute,
and a rare spec's requests can wait near-forever. The continuous engine
launches a padded bucket once the oldest request ages past
``max_wait_ms``, bounding that wait.

Method: one engine serves four registered workloads (2-D uniform, 2-D
equalized, tiles-region texture map, 3-D volume) with a SKEWED mix
(55/25/15/5% — rare specs are where fixed batching hurts). The arrival
trace is seeded and wall-clock-free: exponential (Poisson) gaps in
mean-service units with a 3×-rate burst in the middle third,
workload/priority draws from the same generator; ~20% priority 1.

Replay is EVENT-DRIVEN on a warp clock injected into the engine
(``GLCMEngine(clock=...)``): waiting for the next arrival or deadline is a
clock JUMP (via ``engine.next_deadline()``), while dispatch compute still
elapses real time — so queueing dynamics are exact at any service scale
and the replay costs only the compute, never sleeps. Latency percentiles
come from the engine's own ``stats()``/``latencies()`` surface.

Two operating points per engine mode: 50% offered load (latency regime —
partial batches dominate) and 100% (throughput regime — queues stay full,
both engines mostly launch full batches; the end-of-trace flush drains
fixed-mode stragglers, which UNDERSTATES fixed's true unbounded tail).
``speedups.serve_continuous_vs_fixed`` records ``load50/p99_latency_ratio``
and ``load50/p50_latency_ratio`` (fixed / continuous — higher is better)
plus ``full_load/throughput_ratio`` (continuous / fixed — must stay ≈1:
the deadline must not tax the saturated regime), ratcheted by
``benchmarks.perf_gate``.
"""

import argparse
import sys
import time

import numpy as np

from benchmarks.common import emit, time_fn
from repro.compile_cache import enable_compile_cache
from repro.core.spec import GLCMSpec
from repro.obs.trace import Tracer, set_tracer
from repro.serve.engine import GLCMEngine, GLCMServeConfig

SIZE = 64
LEVELS = 16
BATCH = 8
# (name, spec, shape, traffic share) — shares sum to 1; the 5% volume
# workload is the fixed-batch engine's worst case (its batches ~never fill).
WORKLOADS = (
    ("uniform2d", GLCMSpec(levels=LEVELS, pairs=((1, 0), (1, 45)),
                           quantize="uniform"), (SIZE, SIZE), 0.55),
    ("equalized2d", GLCMSpec(levels=LEVELS, pairs=((1, 0),),
                             quantize="equalized"), (SIZE, SIZE), 0.25),
    ("tiles", GLCMSpec(levels=LEVELS, pairs=((1, 0),), quantize="uniform",
                       region="tiles", region_shape=(32, 32)),
     (SIZE, SIZE), 0.15),
    ("volume", GLCMSpec(levels=LEVELS, pairs=((1, 0),), quantize="uniform",
                        ndim=3), (4, 32, 32), 0.05),
)


def make_trace(n: int, seed: int = 0) -> list[tuple[float, int, int]]:
    """The seeded, wall-clock-free trace: n rows of (gap, workload_index,
    priority), gaps in MEAN-SERVICE units (scaled to seconds at replay).
    Exponential inter-arrivals; the middle third arrives at 3× rate (the
    burst); workloads drawn by their traffic share; ~20% priority 1."""
    rng = np.random.default_rng(seed)
    shares = np.asarray([w[3] for w in WORKLOADS])
    rows = []
    for i in range(n):
        rate = 3.0 if n // 3 <= i < 2 * n // 3 else 1.0
        gap = float(rng.exponential(1.0 / rate))
        wid = int(rng.choice(len(WORKLOADS), p=shares))
        prio = int(rng.random() < 0.2)
        rows.append((gap, wid, prio))
    return rows


class WarpClock:
    """``time.monotonic`` plus a jumpable offset: real compute time still
    elapses (service latencies stay honest), but idle waits are a jump —
    the replay never sleeps."""

    def __init__(self):
        self.offset = 0.0

    def __call__(self) -> float:
        return time.monotonic() + self.offset

    def jump_to(self, t: float) -> None:
        now = self()
        if t > now:
            self.offset += t - now


def _build_engine(max_wait_ms, clock=None, tracer=None) -> tuple[GLCMEngine, list[int]]:
    name0, spec0, shape0, _ = WORKLOADS[0]
    eng = GLCMEngine(
        GLCMServeConfig(
            spec=spec0, image_shape=shape0, batch_size=BATCH,
            max_wait_ms=max_wait_ms, max_results=100_000,
        ),
        clock=clock,
        tracer=tracer,
    )
    wids = [0]
    for name, spec, shape, _ in WORKLOADS[1:]:
        wids.append(eng.register(spec, shape, name=name))
    eng.warmup()
    return eng, wids


def _inputs(seed: int = 1) -> list[np.ndarray]:
    rng = np.random.default_rng(seed)
    return [rng.random(shape, np.float32) * 255 for _, _, shape, _ in WORKLOADS]


def replay(max_wait_ms, trace, unit_s: float, inputs,
           trace_out: str = "") -> tuple[dict, dict]:
    """Event-driven trace replay → ({p50, p95, p99, mean, n, throughput},
    engine stats).  With ``trace_out`` set, the replay runs under a tracer
    sharing the warp clock (so span timestamps live on the simulated
    timeline) and writes Chrome-trace JSON there at the end — load it in
    Perfetto / chrome://tracing."""
    clock = WarpClock()
    tracer = prev = None
    if trace_out:
        # Install globally too, so plan-cache/compile spans from layers that
        # consult get_tracer() land on the same timeline as engine spans.
        tracer = Tracer(enabled=True, clock=clock)
        prev = set_tracer(tracer)
    try:
        eng, wids = _build_engine(max_wait_ms, clock=clock, tracer=tracer)
        start = clock()
        due = start
        for gap, w, prio in trace:
            due += gap * unit_s
            # fire every deadline that falls before the next arrival
            while True:
                nd = eng.next_deadline()
                if nd is None or nd > due:
                    break
                clock.jump_to(nd)
                eng.poll()
            clock.jump_to(due)
            eng.submit(inputs[w], workload=wids[w], priority=prio)
        eng.flush()                      # trace over: drain stragglers now
    finally:
        if tracer is not None:
            set_tracer(prev)
    if tracer is not None:
        tracer.save_chrome(trace_out)
        print(f"# wrote {len(tracer)} spans to {trace_out}", file=sys.stderr)
    span = clock() - start
    lat = np.concatenate([eng.latencies(w, "e2e") for w in wids])
    p50, p95, p99 = np.percentile(lat, (50.0, 95.0, 99.0))
    return (
        {
            "p50": float(p50), "p95": float(p95), "p99": float(p99),
            "mean": float(lat.mean()), "n": int(lat.size),
            "throughput": lat.size / span,
        },
        eng.stats(),
    )


def run(n_requests: int = 240, trace: str = "") -> None:
    trace_out = trace  # `trace` below is the ARRIVAL trace; this is the path
    # Per-workload plan capacity (informational rows)…
    eng, wids = _build_engine(None)
    for (name, _, shape, share), wid in zip(WORKLOADS, wids):
        stack = np.zeros((BATCH, *shape), np.float32)
        us = time_fn(eng._plan_for(eng._workloads[wid], BATCH), stack)
        emit(f"serve_load/capacity/{name}", us / BATCH,
             f"images_per_sec={1e6 / (us / BATCH):.0f}",
             workload=name, batch=BATCH, share=share)
    # …but OFFERED LOAD is calibrated against what the ENGINE actually
    # sustains (plan compute + validation/dispatch overhead): replay a
    # zero-gap saturated prefix through the fixed engine and take its
    # throughput as capacity, so "load 1.0" means exactly saturation.
    cal, _ = replay(None, make_trace(max(64, n_requests // 3)), 0.0, _inputs())
    mean_service_s = 1.0 / cal["throughput"]
    emit("serve_load/capacity/engine", mean_service_s * 1e6,
         f"images_per_sec={cal['throughput']:.0f}")
    # Deadline: the time a batch takes to FILL at full load for an
    # average-share workload — at saturation it ~never fires, below
    # saturation it bounds the wait the fixed engine leaves unbounded.
    max_wait_ms = BATCH * len(WORKLOADS) * mean_service_s * 1e3

    trace = make_trace(n_requests)
    inputs = _inputs()
    results: dict = {}
    for load in (0.5, 1.0):
        unit_s = mean_service_s / load
        for mode, wait in (("continuous", max_wait_ms), ("fixed", None)):
            # --trace captures the continuous 50%-load replay: the regime
            # where partial batches, deadline fires, and queue waits are
            # all visible in one span tree per request.
            capture = trace_out if (mode, load) == ("continuous", 0.5) else ""
            r, st = replay(wait, trace, unit_s, inputs, trace_out=capture)
            results[(mode, load)] = r
            deadline = sum(w["deadline_dispatches"]
                           for w in st["workloads"].values())
            emit(
                f"serve_load/{mode}/load{int(load * 100)}",
                r["mean"] * 1e3,
                f"p99={r['p99']:.1f}ms_tput={r['throughput']:.0f}ips",
                mode=mode, load=load, requests=r["n"],
                latency_p50_ms=round(r["p50"], 3),
                latency_p95_ms=round(r["p95"], 3),
                latency_p99_ms=round(r["p99"], 3),
                throughput_ips=round(r["throughput"], 1),
                batches=st["batches_dispatched"],
                deadline_dispatches=deadline,
                max_wait_ms=None if wait is None else round(wait, 3),
            )

    ratios = (
        ("load50/p99_latency_ratio",
         results[("fixed", 0.5)]["p99"] / results[("continuous", 0.5)]["p99"]),
        ("load50/p50_latency_ratio",
         results[("fixed", 0.5)]["p50"] / results[("continuous", 0.5)]["p50"]),
        ("full_load/throughput_ratio",
         results[("continuous", 1.0)]["throughput"]
         / results[("fixed", 1.0)]["throughput"]),
    )
    for metric, value in ratios:
        emit(f"serve_load/ratio/{metric}", 0.0, f"ratio={value:.2f}",
             serve_metric=metric, ratio=value)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Bursty mixed-spec serving load benchmark."
    )
    ap.add_argument("--requests", type=int, default=240,
                    help="arrival-trace length")
    ap.add_argument("--trace", default="",
                    help="write Chrome-trace JSON of the continuous "
                         "50%%-load replay here (open in Perfetto)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    run(n_requests=args.requests, trace=args.trace)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
