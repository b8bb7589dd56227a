"""Trace a mixed-spec burst end-to-end and export a Perfetto-loadable file.

    PYTHONPATH=src python examples/trace_dispatch.py [out.json]

One GLCMEngine serves a burst of mixed-spec requests with tracing ON: a
:class:`~repro.obs.trace.Tracer` is injected into the engine (sharing its
clock), so every ``submit()`` mints a correlation ID that is carried
through queue wait → padding → copy → bucket launch → readback, producing
one span tree per request plus one per dispatched batch.  The trace is saved
as Chrome ``trace_event`` JSON — open it at https://ui.perfetto.dev or
``chrome://tracing`` — and summarized in the terminal with the
``repro.obs.report`` helpers (per-phase breakdown, dispatch timeline,
an example request tree).
"""

import sys
import time

import numpy as np

from repro.obs.report import load_trace, summarize
from repro.obs.trace import Tracer, set_tracer
from repro.core.spec import GLCMSpec
from repro.serve.engine import GLCMEngine, GLCMServeConfig

SIZE = 64
BATCH = 8

WORKLOADS = (
    ("features2d", GLCMSpec(levels=16, pairs=((1, 0), (1, 45)),
                            quantize="uniform"), (SIZE, SIZE), 0.55),
    ("equalized", GLCMSpec(levels=16, pairs=((1, 0),),
                           quantize="equalized"), (SIZE, SIZE), 0.25),
    ("texture_map", GLCMSpec(levels=16, pairs=((1, 0),), quantize="uniform",
                             region="tiles", region_shape=(32, 32)),
     (SIZE, SIZE), 0.15),
    ("volume", GLCMSpec(levels=16, pairs=((1, 0),), quantize="uniform",
                        ndim=3), (4, 32, 32), 0.05),
)


def main() -> None:
    out = sys.argv[1] if len(sys.argv) > 1 else "dispatch_trace.json"

    # One tracer, injected into the engine AND installed globally so the
    # plan cache's compile/lint spans land on the same timeline.  It starts
    # disabled: warmup's XLA compiles would otherwise stretch the timeline
    # by seconds before the first request arrives.
    tracer = Tracer(enabled=False, clock=time.monotonic)
    prev = set_tracer(tracer)
    try:
        eng = GLCMEngine(GLCMServeConfig(
            spec=WORKLOADS[0][1], image_shape=WORKLOADS[0][2],
            batch_size=BATCH, max_wait_ms=5.0, max_results=4096,
        ), tracer=tracer)
        wids = [0] + [eng.register(spec, shape, name=name)
                      for name, spec, shape, _ in WORKLOADS[1:]]
        eng.warmup()
        tracer.enabled = True          # trace the burst, not the warmup

        rng = np.random.default_rng(0)
        inputs = [rng.random(shape, np.float32) * 255
                  for _, _, shape, _ in WORKLOADS]
        shares = [w[3] for w in WORKLOADS]

        for _ in range(120):
            w = int(rng.choice(len(WORKLOADS), p=shares))
            eng.submit(inputs[w], workload=wids[w],
                       priority=int(rng.random() < 0.2))
            eng.poll()
        eng.flush()
    finally:
        set_tracer(prev)

    tracer.save_chrome(out)
    print(f"wrote {len(tracer)} spans to {out} "
          f"(open in https://ui.perfetto.dev)\n")

    # Same summary the `python -m repro.obs.report` CLI prints: the Chrome
    # export embeds span/parent/correlation ids in args, so the request
    # trees survive the round trip through the file.
    print(summarize(load_trace(out), top=5), end="")


if __name__ == "__main__":
    main()
