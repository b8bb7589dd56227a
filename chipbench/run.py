#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``. Its traffic is
``chipbench/workloads/<cell>.json`` (loop kind, clients, shape, batch and
buckets, deadline, pool), its configuration
``chipbench/configs/<config>.json`` (the GLCM spec as served, the expected
backend, the limit of the comparison), and the configuration's ``kind``
names the module ``chipbench/kinds/<kind>.py`` that makes its pool, builds
its engine, counts its work and holds its reference. The run:

1. makes the cell's request pool on the device from ``--seed``, builds a
   ``GLCMEngine`` for the configuration, checks that every bucket's served
   plan resolves to the expected Pallas backend, and warms up the cell's
   own bucket shapes (set-up: process start to the window);
2. drives ``submit`` / ``result`` from the client's side with the cell's
   loop (``chipbench/traffic/<loop>.py``) for ``--seconds``;
3. with ``--trace 1``, traces that window with the profiler and reads each
   per-layer metric with its reader (``chipbench/metrics/<metric>.py``);
4. frees the engine and compares every answer of the window with the
   kind's plain reference, computed once per pool entry.

The last line of stdout is one JSON object; the numbers compared for
``correct`` come last there and on stderr. Without a TPU, or with fewer
chips than the cell asks for, it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import contextlib
import dataclasses
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

import numpy as np  # noqa: E402

from chipbench import data, kinds, trace  # noqa: E402
from chipbench.metrics import reader  # noqa: E402
from chipbench.traffic import RealClock, loop  # noqa: E402

class HarnessError(RuntimeError):
    pass


def log(msg: str) -> None:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)


def load_json(rel: str) -> dict:
    return json.loads((ROOT / rel).read_text())


def load_cell(name: str):
    """(benchmark, workload entry, cell file, configuration file)."""
    bench = load_json("BENCHMARK.json")
    entries = [w for w in bench["workloads"] if w["name"] == name]
    if not entries:
        raise HarnessError(f"no workload {name!r} in BENCHMARK.json")
    cell = load_json(f"chipbench/workloads/{name}.json")
    config = load_json(f"chipbench/configs/{cell['config']}.json")
    return bench, entries[0], cell, config


def enable_cache() -> None:
    """JAX's persistent compile cache in the checkout (or where
    JAX_COMPILATION_CACHE_DIR says), holding every program, however quick
    to compile."""
    import jax

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def require_accelerator(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise HarnessError(f"no TPU: jax found {devs[0].platform!r}")
    if len(devs) < chips:
        raise HarnessError(f"the cell needs {chips} chips, jax found {len(devs)}")
    return devs[0]


def build_engine(cell: dict, config: dict):
    return kinds.of(config).build_engine(cell, config)


def check_backend(engine, cell: dict, config: dict) -> str:
    """The plan the engine serves each bucket with resolves to the
    configuration's backend, on the device and compiled (not the host path,
    not interpret mode). The engine takes its plans from the plan cache
    under its own spec, shape and feature set, so these are its plans."""
    from repro.core.plan import compile_plan
    from repro.kernels.ops import should_interpret

    expect = config["expect_backend"]
    for b in cell["buckets"]:
        plan = compile_plan(engine.spec, (b, *engine.cfg.image_shape),
                            features=engine.cfg.features)
        if plan.spec.scheme != expect:
            raise HarnessError(f"bucket {b} resolved to {plan.spec.scheme!r}, "
                               f"expected {expect!r}")
        if plan.backend.caps.host_native:
            raise HarnessError(f"{plan.spec.scheme!r} is a host backend")
    if expect.startswith("pallas") and should_interpret():
        raise HarnessError("Pallas would run in interpret mode")
    return expect


class ProgramCounter:
    """Programs traced or compiled while ``on`` (the window should build none)."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.on = False
        self.n = 0

    def __call__(self, event, duration_secs, **kwargs):
        if self.on and event in self.EVENTS:
            self.n += 1


@dataclasses.dataclass
class Context:
    """What a per-layer reader may read."""
    cell: dict
    config: dict
    records: list
    served: int                 # requests the engine served in the window
    phase_ms: dict              # engine phase (pad/launch/readback) → ms in the window
    trace: trace.Reduced | None
    device_kind: str
    work: tuple                 # (ops, bytes) of one request
    span_ns: dict = dataclasses.field(default_factory=dict)  # program span → ns in the window
    stats: dict = dataclasses.field(default_factory=dict)    # window_stats of the engine
    notes: dict = dataclasses.field(default_factory=dict)


GAUGES = ("batch_size", "ndim", "queue_depth")


def window_stats(before: dict, after: dict) -> dict:
    """The engine's ``stats()`` entry of one workload over the window: each
    count as its change (``GAUGES`` as they stand at the end), each
    per-batch sample set (``pad_ms``, ``h2d_ms``, ...) as its number and
    total of the window's samples, everything else as it stands."""
    out = {}
    for key, v in after.items():
        b = before.get(key)
        if isinstance(v, int) and not isinstance(v, bool) and key not in GAUGES:
            out[key] = v - b
        elif isinstance(v, dict) and {"n", "mean"} <= set(v):
            out[key] = {"n": v["n"] - b["n"], "total": v["mean"] * v["n"] - b["mean"] * b["n"]}
        else:
            out[key] = v
    return out


def end_to_end(window, cell: dict, setup_s: float) -> dict:
    """Every end-to-end metric the harness can report; ``BENCHMARK.json``
    names those a cell reports."""
    voxels = float(np.prod(cell["shape"]))
    return {
        "throughput_mvox_s": voxels * len(window.records) / window.seconds / 1e6,
        "setup_s": setup_s,
    }


def reports(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def reference_answers(kind, pool, indices, config) -> dict:
    """The kind's reference answer of each named pool entry."""
    return {i: kind.reference(pool[i], config) for i in sorted(set(indices))}


def reference_errors(kind, records, want) -> list[float]:
    """The kind's error of each record's answer against the reference
    answer of its pool entry."""
    return [kind.error(r.answer, want[r.pool_index]) for r in records]


@dataclasses.dataclass
class Prepared:
    bench: dict
    cell: dict
    config: dict
    kind: object
    device: object
    pool: list
    engine: object


def warm_up(engine, pool, buckets, rounds: int = 2) -> None:
    """Compile every bucket, then serve real pool entries through
    submit/flush/result at each bucket size, so that the window's first
    requests find the host path as warm as its later ones."""
    engine.warmup(dtype=pool[0].dtype)
    stream = data.request_stream(pool, 0)
    for b in buckets:
        for _ in range(rounds):
            tickets = [engine.submit(next(stream)[1]) for _ in range(b)]
            engine.flush()
            for t in tickets:
                engine.result(t)


def prepare(name: str, seed: int) -> Prepared:
    """Set-up: the cell's files, the device, the pool, the engine checked
    and warmed up on the cell's own bucket shapes."""
    import jax

    bench, entry, cell, config = load_cell(name)
    kind = kinds.of(config)
    enable_cache()
    dev = require_accelerator(entry["chips"])
    log(f"device platform={dev.platform} kind={dev.device_kind} "
        f"count={len(jax.devices())} at {time.monotonic() - T_START:.3f} s")
    pool = kind.make_pool(cell["pool"], cell["shape"], seed)
    log(f"pool of {len(pool)} made by {time.monotonic() - T_START:.3f} s")
    engine = build_engine(cell, config)
    log(f"backend={check_backend(engine, cell, config)} buckets={cell['buckets']}")
    warm_up(engine, pool, cell["buckets"])
    log(f"engine warm by {time.monotonic() - T_START:.3f} s")
    return Prepared(bench, cell, config, kind, dev, pool, engine)


def run_cell(name: str, seed: int, seconds: float, traced: bool,
             trace_out: str | None = None) -> dict:
    import jax

    prep = prepare(name, seed)
    bench, cell, config, kind = prep.bench, prep.cell, prep.config, prep.kind
    dev, pool = prep.device, prep.pool
    engine = prep.engine
    del prep

    from repro.core.plan import plan_cache_stats

    programs = ProgramCounter()
    jax.monitoring.register_event_duration_secs_listener(programs)
    misses = plan_cache_stats()["misses"]
    before = engine.stats()["workloads"][0]
    gc.collect()
    gc.freeze()  # set-up's objects (JAX, the pool) leave the collector's scans
    log_dir = None
    if traced:
        log_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # the harness's own spans say what the host did
        jax.profiler.start_trace(log_dir, profiler_options=options)

    def span(label):
        if not traced:
            return contextlib.nullcontext()
        return jax.profiler.TraceAnnotation(trace.PREFIX + label)

    setup_s = time.monotonic() - T_START
    programs.on = True
    with span(trace.WINDOW):
        window = loop(cell["loop"]).run(engine, data.request_stream(pool, seed), cell,
                                        seconds, seed, RealClock, span)
    programs.on = False
    reduced = None
    if traced:
        jax.profiler.stop_trace()
        t0 = time.monotonic()
        loaded = trace.load(trace.find_xplane(log_dir))
        shutil.rmtree(log_dir, ignore_errors=True)
        if trace_out:
            Path(trace_out).write_text(json.dumps(loaded))
        reduced = trace.reduce(loaded)
        log(f"trace read in {time.monotonic() - t0:.3f} s")
    misses = plan_cache_stats()["misses"] - misses
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    log(f"plan_cache_misses_in_window={misses} programs_built_in_window={programs.n}")
    log(f"peak_bytes_in_use={peak}")
    log(f"requests={len(window.records)} window_s={window.seconds}")

    stats = engine.stats()["workloads"][0]
    in_window = window_stats(before, stats)
    batches = in_window["batches"]
    phase_ms = {ph: in_window[f"{ph}_ms"]["total"] for ph in ("pad", "launch", "readback")}
    log(f"engine ms per batch in window ({batches} batches): "
        + " ".join(f"{ph}={ms / max(batches, 1):.3f}" for ph, ms in phase_ms.items()))

    metrics, extra = {}, {}
    if not traced:
        values = end_to_end(window, cell, setup_s)
        for m in bench["end_to_end"]:
            if reports(m, name):
                metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        ctx = Context(
            cell=cell, config=config, records=window.records,
            served=in_window["served"], phase_ms=phase_ms, trace=reduced,
            device_kind=dev.device_kind, work=kind.work(cell, config, pool),
            span_ns=reduced.span_ns, stats=in_window,
        )
        log(f"engine stats: {json.dumps(stats, default=str)}")
        for m in bench["per_layer"]:
            if reports(m, name):
                value = reader(m["name"])(ctx)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        for key, bound in ctx.notes.items():
            log(f"{key}={bound}")
        extra = {"roofline_bound": ctx.notes}

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result = {}
    if reduced is not None:
        device["busy_s"] = reduced.busy_ns / 1e9
        device["window_s"] = reduced.window_ns / 1e9
        result["breakdown"] = {
            "device_ops": [[k, ns / 1e9] for k, ns in reduced.top_ops()],
            "idle_gaps": [[k, ns / 1e9] for k, ns in reduced.idle_by_label()],
        }
        log(f"longest idle gaps (ms): "
            f"{[(k, ns / 1e6) for k, ns in sorted(reduced.gaps, key=lambda g: -g[1])[:10]]}")
    del engine
    gc.collect()

    gc.unfreeze()
    t0 = time.monotonic()
    want = reference_answers(kind, pool, [r.pool_index for r in window.records], config)
    errors = reference_errors(kind, window.records, want)
    log(f"reference compared {len(errors)} answers in {time.monotonic() - t0:.3f} s")
    limit = float(config["feature_err_limit"])
    failed = sum(not e <= limit for e in errors)
    worst = max(errors) if errors else float("inf")
    if errors:
        rec = window.records[int(np.argmax(errors))]
        log(f"largest feature_err: pool entry {rec.pool_index}, "
            f"{kind.worst(rec.answer, want[rec.pool_index])}")
    checks = {"feature_err": {"value": worst, "limit": limit}}
    log(f"check feature_err={worst!r} limit={limit!r} failed={failed}")
    return {"correct": bool(errors) and failed == 0, "attempted": len(window.records),
            "failed": failed, "metrics": metrics, "device": device, **result, **extra,
            "checks": checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-out", help="write the trace, read into plain JSON, here")
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                          args.trace_out)
    except (HarnessError, ImportError, FileNotFoundError) as exc:
        log(f"error: {type(exc).__name__}: {exc}")
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
