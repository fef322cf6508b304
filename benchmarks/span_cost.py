"""Cost of one span of ``repro.obs.trace`` on the host CPU, in nanoseconds.

    python -m benchmarks.span_cost [--n 200000]

Times an empty ``with tracer.span(...)`` and ``with tracer.counted_span(...)``
(the kind that adds two ``getrusage`` calls), each with the attributes the
solver's iteration spans carry, on a disabled tracer, on an enabled one,
and on an enabled one while a ``jax.profiler`` capture runs with its Python
tracer off, as the chip benchmark's traced window runs (each span then also
records a profiler event). Prints one JSON line; best of 3 passes.
"""

from __future__ import annotations

import argparse
import json
import tempfile
import time

from repro.obs.trace import Tracer


def ns_per_span(open_span, n: int) -> float:
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter_ns()
        for i in range(n):
            with open_span("solver.iterate", solver="power", iteration=i):
                pass
        best = min(best, (time.perf_counter_ns() - t0) / n)
    return best


def measure(n: int) -> dict:
    off, on = Tracer(enabled=False), Tracer()
    out = {
        "disabled.span": ns_per_span(off.span, n),
        "disabled.counted_span": ns_per_span(off.counted_span, n),
        "enabled.span": ns_per_span(on.span, n),
        "enabled.counted_span": ns_per_span(on.counted_span, n),
    }
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            out["profiled.span"] = ns_per_span(on.span, n // 10)
            out["profiled.counted_span"] = ns_per_span(on.counted_span, n // 10)
        finally:
            jax.profiler.stop_trace()
    return {k: round(v, 1) for k, v in out.items()}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--n", type=int, default=200_000, help="spans per pass")
    args = ap.parse_args(argv)
    print(json.dumps({"ns_per_span": measure(args.n)}))


if __name__ == "__main__":
    main()
