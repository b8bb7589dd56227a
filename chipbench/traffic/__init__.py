"""Traffic loops: one module per loop kind, named by the ``loop`` key of a
cell file. Each exposes ``run(engine, requests, cell, seconds, seed,
clock, span) -> Window``: ``requests`` yields (pool index, raw array)
forever, ``clock`` has ``now()`` (monotonic seconds), and ``span(name)``
is a context manager that marks the harness's own host work in the
trace."""

from __future__ import annotations

import dataclasses
import importlib
import time

import numpy as np


@dataclasses.dataclass
class Record:
    pool_index: int
    sent: float       # when it was submitted
    done: float       # when its answer was in the client's hands
    answer: np.ndarray


@dataclasses.dataclass
class Window:
    start: float
    end: float
    records: list

    @property
    def seconds(self) -> float:
        return self.end - self.start


class RealClock:
    now = staticmethod(time.monotonic)


def loop(kind: str):
    return importlib.import_module(f"chipbench.traffic.{kind}")
