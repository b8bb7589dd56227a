"""The benchmark's cells shrunk to sizes a CPU test run holds, for driving
``chipbench.run`` without a chip: the same files, loop, engine and
reference, at the size and with the backend that each configuration's
kind gives for the CPU (``cpu_cell``), in place of the Pallas kernel."""

import json
from pathlib import Path

import jax

from chipbench import kinds, run

BENCH = json.loads((Path(run.__file__).resolve().parents[1] / "BENCHMARK.json").read_text())
CELLS = sorted(w["name"] for w in BENCH["workloads"])


def shrink(monkeypatch):
    """Point chipbench.run at the shrunk cells, on the CPU."""
    real = run.load_cell

    def load_cell(name):
        bench, entry, cell, config = real(name)
        cell, backend = kinds.of(config).cpu_cell(cell, config)
        return bench, entry, cell, dict(config, expect_backend=backend)

    monkeypatch.setattr(run, "load_cell", load_cell)
    monkeypatch.setattr(run, "require_accelerator", lambda chips: jax.devices()[0])
