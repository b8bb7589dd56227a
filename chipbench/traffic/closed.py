"""Closed loop: ``clients`` callers, each waiting for its answer before it
sends the next request.

The engine dispatches synchronously, so the clients move in rounds: all
of them submit (a full batch launches on the last submit), then all take
their answers. Requests are sent until ``seconds`` have passed since the
window opened; the round under way then finishes, and the window closes
when its last answer arrives.
"""

from __future__ import annotations

from chipbench.traffic import Record, Window


def run(engine, requests, cell: dict, seconds: float, seed: int, clock, span) -> Window:
    clients = int(cell["clients"])
    records: list[Record] = []
    start = clock.now()
    while clock.now() - start < seconds:
        with span("generate"):
            items = [next(requests) for _ in range(clients)]
        sent = clock.now()
        with span("submit"):
            tickets = [engine.submit(image) for _, image in items]
        with span("result"):
            answers = [engine.result(t) for t in tickets]
        done = clock.now()
        records.extend(Record(idx, sent, done, answer)
                       for (idx, _), answer in zip(items, answers))
    return Window(start=start, end=clock.now(), records=records)
