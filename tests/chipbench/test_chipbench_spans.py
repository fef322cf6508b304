"""The per-layer readers of the program's own spans.

Each reader runs on a hand-built list of span records, as ``repro.obs.trace``
writes them, and on the lists that must give nothing: spans dropped, and a
program without the span or counter it reads. Then one traced run of a cell
at a small size on the CPU, through ``run_cell``, reports all four.
"""

import pytest

from chipbench import run, spans as program
from chipbench.drivers import power_solve

READERS = ("setup_ms.solve", "fingerprint_ms.solve", "step_host_ms.solve",
           "host_sys_ms.solve")


def _module(metric):
    return run.load_reader(metric).__globals__


def _span(id_, name, parent, dur_s, **attrs):
    rec = {"name": name, "id": id_, "parent": parent, "root": 1, "ts": 0.0,
           "dur_s": dur_s, "thread": 1}
    if attrs:
        rec["attrs"] = attrs
    return rec


def _solve(first, sys_s=0.010, usage=True):
    """One two-iteration solve; ids from ``first``."""
    counters = {"user_s": 0.5, "sys_s": sys_s, "minflt": 9, "majflt": 0, "nvcsw": 1,
                "nivcsw": 0} if usage else {}
    i = first
    return [
        _span(i + 2, "solver.count_nnz", i + 1, 0.030, **counters),
        _span(i + 4, "session.fingerprint", i + 3, 0.200, bytes=8, **counters),
        _span(i + 5, "session.optimize", i + 3, 0.010),
        _span(i + 3, "session.serve", i + 1, 0.215),
        _span(i + 1, "solver.setup", i, 0.250, **counters),
        _span(i + 7, "kernel.execute", i + 6, 0.100, fmt="csr"),
        _span(i + 6, "solver.iterate", i, 0.104, iteration=1, **counters),
        _span(i + 9, "kernel.execute", i + 8, 0.100, fmt="csr"),
        _span(i + 8, "solver.iterate", i, 0.106, iteration=2, **counters),
        _span(i, "solver.solve", None, 0.470, **counters),
    ]


TWO_SOLVES = _solve(1, sys_s=0.010) + _solve(11, sys_s=0.030)


@pytest.mark.parametrize("metric, expected", [
    ("setup_ms.solve", 2 * 250 / 4),
    ("fingerprint_ms.solve", 2 * 200 / 4),
    ("step_host_ms.solve", 2 * (4 + 6) / 4),
    ("host_sys_ms.solve", (10 + 30) / 4),
])
def test_reader_values(metric, expected):
    assert _module(metric)["value"](TWO_SOLVES, 0, 4) == pytest.approx(expected)


@pytest.mark.parametrize("metric", READERS)
def test_readers_give_nothing_when_spans_were_dropped(metric):
    assert _module(metric)["value"](TWO_SOLVES, 1, 4) is None


@pytest.mark.parametrize("metric", READERS)
def test_readers_give_nothing_without_spans_or_spmvs(metric):
    value = _module(metric)["value"]
    assert value([], 0, 4) is None
    assert value(TWO_SOLVES, 0, 0) is None


@pytest.mark.parametrize("metric", READERS)
def test_readers_give_nothing_for_a_program_without_the_spans(metric):
    """A program that spans only the solve and its iterations, with no
    counters, no set-up and no kernel span, as the program did before."""
    older = [s for s in _solve(1, usage=False) if s["name"] in ("solver.solve", "solver.iterate")]
    assert _module(metric)["value"](older, 0, 2) is None


def test_a_kernel_span_outside_the_iterations_is_not_subtracted():
    stray = _span(99, "kernel.execute", None, 5.0, fmt="csr")
    value = _module("step_host_ms.solve")["value"]
    assert value(TWO_SOLVES + [stray], 0, 4) == value(TWO_SOLVES, 0, 4)


def test_the_readers_read_the_programs_tracer():
    from repro.obs.trace import get_tracer

    tracer = get_tracer()
    tracer.clear()
    for rec in _solve(1):
        tracer._collect(rec)
    try:
        assert program.window() == (tracer.spans(), 0)
        ctx = run.TraceContext(reduction=None, spmvs=2, least_s=float("nan"))
        assert run.load_reader("setup_ms.solve")(ctx) == pytest.approx(125.0)
    finally:
        tracer.clear()


def test_a_traced_run_on_the_cpu_reports_the_four(monkeypatch):
    """``run_cell`` with the profiler on, at a small size. The CPU's trace
    has no device plane, which the trace reduction refuses, so an empty one
    is added; the four readers do not read the device."""
    from chipbench import tracing

    real_load = tracing.load

    def load_with_a_device(log_dir):
        trace = real_load(log_dir)
        trace.devices.setdefault("/device:TPU:0", {})
        return trace

    monkeypatch.setattr(tracing, "load", load_with_a_device)
    tuner = power_solve.build_tuner({"tuner": {"scale": 0.0008, "train_matrices": 2}})
    res = run.run_cell("rim.solve", 2**31 + 5, 0.3, True, require_chip=False, scale=0.03,
                       tuner=tuner)
    assert res["correct"]
    metrics = res["metrics"]
    for name in READERS:
        assert metrics[name]["unit"] == "ms"
        assert metrics[name]["value"] >= 0, name
    assert metrics["setup_ms.solve"]["value"] > metrics["fingerprint_ms.solve"]["value"] > 0
    assert metrics["step_host_ms.solve"]["value"] > 0
