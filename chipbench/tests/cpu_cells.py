"""The benchmark's cells shrunk to sizes a CPU test run holds, for driving
``chipbench.run`` without a chip: the same files, loop, engine and
reference, with small shapes and the CPU's backend expected in place of
the Pallas kernel."""

import jax

from chipbench import run

SMALL = {
    "paper_table3.16k_closed": {"shape": [48, 40]},
}


def shrink(monkeypatch):
    """Point chipbench.run at the shrunk cells, on the CPU."""
    real = run.load_cell

    def load_cell(name):
        bench, entry, cell, config = real(name)
        cell = dict(cell, **SMALL[name])
        return bench, entry, cell, dict(config, expect_backend="onehot")

    monkeypatch.setattr(run, "load_cell", load_cell)
    monkeypatch.setattr(run, "require_accelerator", lambda chips: jax.devices()[0])
