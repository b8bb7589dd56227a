"""Configuration kinds: one module per kind, named by the ``kind`` key of a
configuration file (``chipbench/kinds/<kind>.py``). A kind holds what
belongs to one sort of deployment: how its requests are made, how its
engine is built, the work of one request for the roofline, and its plain
reference with the comparison that decides ``correct``. Each exposes:

  make_pool(pool, shape, seed)     the cell's request pool, host arrays,
                                   the same for the same seed
  build_engine(cell, config)       the ``GLCMEngine`` that serves the cell
  work(cell, config, pool)         (ops, bytes) of one request
  reference(raw, config)           the answer wanted for one pool entry,
                                   from the raw request alone
  error(got, want)                 one float, compared with the
                                   configuration's ``feature_err_limit``
  worst(got, want)                 where ``error`` is largest, for the log
  cpu_cell(cell, config)           (the cell shrunk to a CPU test's size,
                                   the backend the CPU serves it with)

The helpers below build a ``GLCMSpec`` and an engine from a configuration
file, for every kind alike.
"""

from __future__ import annotations

import importlib


def of(config: dict):
    """The kind module of a configuration file."""
    return importlib.import_module(f"chipbench.kinds.{config['kind']}")


def _tuples(value):
    """JSON lists as the tuples a frozen spec takes, nested ones too."""
    if isinstance(value, list):
        return tuple(_tuples(v) for v in value)
    return value


def glcm_spec(config: dict):
    """The ``GLCMSpec`` of every field in the configuration's ``spec``."""
    from repro.core.spec import GLCMSpec

    return GLCMSpec(**{k: _tuples(v) for k, v in config["spec"].items()})


def serve_engine(cell: dict, config: dict, features):
    """A ``GLCMEngine`` serving the configuration's spec at the cell's
    shape, batch, buckets and deadline, with ``features`` per offset."""
    from repro.serve.engine import GLCMEngine, GLCMServeConfig

    return GLCMEngine(GLCMServeConfig(
        spec=glcm_spec(config), image_shape=tuple(cell["shape"]),
        batch_size=cell["batch"], buckets=tuple(cell["buckets"]), features=features,
        max_wait_ms=cell["max_wait_ms"], stats_window=1 << 20,
    ))
