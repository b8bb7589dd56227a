#!/usr/bin/env python3
"""Readings that the limit on ``feature_err`` is set from, on the chip.

    python chipbench/control.py --workload <cell> --seeds 1,2,3 --seconds 3

In one process, with one engine for the cell: for each seed, the cell's
pool is made, the cell's own loop serves it for a short window at the
cell's load, and every answer is compared with the float64 reference, as
a benchmark run does (the program's reading). Two readings come from the
reference put in the program's place, compared the same way for the same
pool entries:

  control    the answer computed in bfloat16, the step below the float32
             the configuration states
  uncounted  the answer of a vote that leaves the last eighth of every row
             uncounted, as a kernel that skips the last of the eight
             2048-lane chunks of a 16384-wide row would

One JSON line per seed, then the lower reading (largest program reading)
and the smallest reading of each planted fault. The benchmark's own runs
never run this.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import data, reference, run  # noqa: E402
from chipbench.traffic import RealClock, loop  # noqa: E402


def uncounted_counts(raw, config):
    """Counts with the last eighth of every row left out; the binning still
    spans the whole request, as the program's range reduction does."""
    spec = config["spec"]
    q = reference.quantize(raw, spec["levels"])
    w = q.shape[-1]
    offs = reference.offsets(spec["pairs"], raw.ndim)
    return reference.counts(q[..., :w - w // 8], spec["levels"], offs)


def fault_errors(pool, indices, config) -> tuple[dict, dict]:
    """(reference answers, {fault: [error of each entry]}) of the named pool
    entries: each entry's counts are taken once, and its answer computed in
    float64 and in bfloat16 from them."""
    want, errors = {}, {"control": [], "uncounted": []}
    for i in sorted(set(indices)):
        counts = reference.raw_counts(pool[i], config)
        want[i] = reference.answer(counts, config)
        errors["control"].append(reference.feature_error(
            reference.answer(counts, config, reference.to_bfloat16), want[i]))
        errors["uncounted"].append(reference.feature_error(
            reference.answer(uncounted_counts(pool[i], config), config), want[i]))
    return want, errors


def readings(name: str, seeds, seconds: float) -> list[dict]:
    prep = None
    rows = []
    for seed in seeds:
        if prep is None:
            prep = run.prepare(name, seed)
            pool = prep.pool
        else:
            pool = data.make_pool(prep.cell["pool"], prep.cell["shape"], seed)
        window = loop(prep.cell["loop"]).run(
            prep.engine, data.request_stream(pool, seed), prep.cell, seconds, seed,
            RealClock, lambda _: contextlib.nullcontext())
        want, faults = fault_errors(pool, [r.pool_index for r in window.records],
                                    prep.config)
        program = run.reference_errors(prep.kind, window.records, want)
        row = {"seed": seed, "requests": len(window.records), "program_err": max(program),
               **{f"{k}_err": max(v) for k, v in faults.items()},
               **{f"{k}_entries": v for k, v in faults.items()}}
        print(json.dumps(row), flush=True)
        rows.append(row)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    try:
        rows = readings(args.workload, [int(s) for s in args.seeds.split(",")],
                        args.seconds)
    except run.HarnessError as exc:
        run.log(f"error: {exc}")
        return 2
    _, _, _, config = run.load_cell(args.workload)
    print(json.dumps({"lower": max(r["program_err"] for r in rows),
                      "control": min(r["control_err"] for r in rows),
                      "uncounted": min(r["uncounted_err"] for r in rows),
                      "limit": config["feature_err_limit"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
