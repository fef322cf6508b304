"""The CG cell ``hpcg104.cg``: its stencil copy, spec, driver, reference,
check and readers.

The cell runs here at ``cpu_scale`` (an 8 x 8 x 8 grid) on the CPU, with a
small tuner and the Pallas kernels in interpret mode, through the same
``run_cell`` the chip runs, minus its look for a chip.
"""

import json
from dataclasses import replace

import numpy as np
import pytest

from chipbench import cg_reference, readings, roofline, run, stencil, tracing
from chipbench.drivers import cg_solve

CELL = "hpcg104.cg"
SEED = 2**31 + 77
METRICS = ("kernel_ms.cg", "xla_ms.cg", "spmv_roofline.cg", "idle_share.cg",
           "fingerprint_ms.cg", "vector_ms.cg")


@pytest.fixture(scope="module")
def cell():
    return run.load_cell(CELL)


@pytest.fixture(scope="module")
def tuner():
    return cg_solve.build_tuner({"tuner": {"scale": 0.0008, "train_matrices": 2}})


def _run(cell, tuner):
    return run.run_cell(CELL, SEED, 0.3, False, require_chip=False, scale=cell.cpu_scale,
                        tuner=tuner)


def _over(res):
    return {k for k, c in res["checks"].items() if c["value"] > c["limit"]}


# ------------------------------------------------------------- the stencil
@pytest.mark.parametrize("grid", [(6, 5, 4), (3, 3, 3), (2, 7, 3), (8, 8, 8)])
def test_the_stencil_copy_equals_the_programs_generator(grid):
    from repro.sparse.generate import hpcg_stencil

    ours, theirs = stencil.generate(*grid), hpcg_stencil(*grid)
    assert ours.shape == theirs.shape
    for field in ("indptr", "indices", "data"):
        a, b = getattr(ours, field), getattr(theirs, field)
        assert a.dtype == b.dtype and np.array_equal(a, b), field
    assert ours.nnz == stencil.entries(*grid)


def test_the_configuration_is_hpcgs_grid(cell):
    from repro.sparse.generate import HPCG_GRID

    cfg = cell.config
    assert tuple(cfg["grid"]) == HPCG_GRID == stencil.grid(cfg["grid"]) == (104, 104, 104)
    assert cfg["n"] == 104**3 == 1_124_864
    assert cfg["nnz"] == stencil.entries(*cfg["grid"]) == (3 * 104 - 2) ** 3 == 29_791_000
    assert cfg["reduced"] == [] and cfg["dtype"] == "float32"
    assert stencil.grid(cfg["grid"], cell.cpu_scale) == (8, 8, 8)


def test_inputs_are_sparse_and_the_seed_draws_only_the_right_hand_sides(cell):
    a = cg_solve.inputs(cell.config, SEED, cell.cpu_scale)
    b = cg_solve.inputs(cell.config, SEED + 1, cell.cpu_scale)
    assert isinstance(a, stencil.Csr) and a.nnz == stencil.entries(8, 8, 8)
    assert all(np.array_equal(getattr(a, f), getattr(b, f)) for f in ("indptr", "indices", "data"))
    exact = cg_reference.operator(a)
    rhs = [cg_solve.rhs(exact, a.shape[0], SEED, i, 0.1) for i in range(2)]
    assert not np.allclose(rhs[0], rhs[1])
    assert np.array_equal(rhs[0], cg_solve.rhs(exact, a.shape[0], SEED, 0, 0.1))
    assert not np.allclose(rhs[0], cg_solve.rhs(exact, a.shape[0], SEED, 0, 0.1, stream=2))
    # b = A x* with x* near 1: the solution the solve should approach
    x_star = 1.0 + 0.1 * np.random.default_rng([SEED, 1, 0]).standard_normal(a.shape[0])
    np.testing.assert_allclose(rhs[0], exact @ x_star)


# ------------------------------------------------------------ the reference
def test_the_reference_is_textbook_cg_and_solves_the_system():
    mat = stencil.generate(4, 3, 3)
    dense = cg_reference.operator(mat).toarray()
    b = np.random.default_rng(1).standard_normal(mat.shape[0])
    x, residuals = cg_reference.CgReference(mat).solve(b, 8)
    # the same recurrence written out on the dense matrix
    xd, r = np.zeros_like(b), b.copy()
    p, rr = r.copy(), r @ r
    for _ in range(8):
        Ap = dense @ p
        alpha = rr / (p @ Ap)
        xd, r = xd + alpha * p, r - alpha * Ap
        rr, old = r @ r, rr
        p = r + rr / old * p
    np.testing.assert_allclose(x, xd, rtol=1e-12, atol=1e-12)
    assert residuals[-1] == pytest.approx(np.linalg.norm(r) / np.linalg.norm(b))
    x_full, res_full = cg_reference.CgReference(mat).solve(b, 60)
    np.testing.assert_allclose(x_full, np.linalg.solve(dense, b), rtol=1e-9, atol=1e-9)
    assert res_full[-1] < 1e-12


def test_compulsory_bytes_of_the_cell_by_hand(cell):
    n, nnz = cell.config["n"], cell.config["nnz"]
    # every float32 value once, x once and y once: 4 (29,791,000 + 2 x 1,124,864)
    # = 4 x 32,040,728
    assert roofline.compulsory_bytes(nnz, n, n) == 4 * (29_791_000 + 2 * 1_124_864) == 128_162_912
    peak = roofline.peaks("TPU v5 lite")
    assert roofline.least_seconds(nnz, n, n, peak) == pytest.approx(156.49e-6, rel=1e-3)


# -------------------------------------------------------------- the spec
def test_the_cell_keeps_the_spec_and_the_driver_contract(cell):
    from chipbench import drivers

    spec = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    (entry,) = [w for w in spec["workloads"] if w["name"] == CELL]
    assert (entry["config"], entry["traffic"], entry["chips"]) == ("hpcg104", "cg", 1)
    assert cell.traffic["driver"] == "cg_solve" and cell.driver is cg_solve
    assert drivers.contract_faults(cg_solve, cell.limits) == []
    assert set(cell.limits) == set(cg_solve.CHECKS)
    assert {m["name"] for m in cell.end_to_end} == {"spmv_ms", "tune_s", "setup_s"}
    assert tuple(m["name"] for m in cell.per_layer) == METRICS
    assert all(m["workloads"] == [CELL] and m["moves"] == "spmv_ms" for m in cell.per_layer)
    assert 0 < cell.cpu_scale < 1
    for other in ("human_gene2.solve", "rim.solve"):
        assert not {m["name"] for m in run.load_cell(other).per_layer} & set(METRICS)


# ------------------------------------------------------------ the decision
def test_a_sound_run_is_correct(cell, tuner):
    res = _run(cell, tuner)
    assert res["correct"] is True
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == set(cg_solve.CHECKS)
    assert not _over(res)
    assert set(res["metrics"]) == {"spmv_ms", "tune_s", "setup_s"}


def _control_request(self, i):
    # the bfloat16 reference answers in the program's place
    low = cg_reference.CgReference(self.mat, precision="bfloat16")
    b = self._rhs(i)
    x, residuals = low.solve(b, self.traffic["max_iters"])
    return cg_solve.Solve(b, x, self.traffic["max_iters"], residuals)


def test_the_bfloat16_control_in_the_programs_place_is_not_correct(cell, tuner, monkeypatch):
    monkeypatch.setattr(cg_solve.Program, "request", _control_request)
    res = _run(cell, tuner)
    assert res["correct"] is False
    assert res["failed"] == res["attempted"] >= 1
    assert {"solution_rel_err", "residual_gap"} & _over(res)


def test_the_control_readings_are_not_correct(cell):
    got = readings.control_readings(cell, SEED, scale=cell.cpu_scale)
    assert got["correct"] is False
    assert got["solution_rel_err"] > cell.limits["solution_rel_err"]


def _altered(answer, i):
    answer.x = answer.x.copy()
    answer.x[answer.x.size // 2] *= 1.01
    return answer


def _shifted_history(answer, i):
    # a history that claims each iteration's progress one iteration early
    answer.residuals = answer.residuals[1:] + answer.residuals[-1:]
    return answer


@pytest.mark.parametrize("fault, over", [
    (_altered, {"solution_rel_err", "true_residual_gap"}),
    (_shifted_history, {"residual_gap"}),
])
def test_a_planted_fault_is_not_correct(cell, tuner, monkeypatch, fault, over):
    original = cg_solve.Program.request
    monkeypatch.setattr(cg_solve.Program, "request", lambda self, i: fault(original(self, i), i))
    res = _run(cell, tuner)
    assert res["correct"] is False
    assert res["failed"] == res["attempted"] >= 1
    assert over <= _over(res)


def test_the_previous_answer_returned_again_is_not_correct(cell, tuner, monkeypatch):
    original = cg_solve.Program.request

    def request(self, i):
        fresh = original(self, i)
        if i == 0:
            self._first = fresh
            return fresh
        return replace(self._first, b=fresh.b)  # solve 0's answer for solve i's b

    monkeypatch.setattr(cg_solve.Program, "request", request)
    res = run.run_cell(CELL, SEED, 2.5, False, require_chip=False, scale=cell.cpu_scale,
                       tuner=tuner)
    assert res["attempted"] >= 2
    assert res["correct"] is False
    assert res["failed"] == res["attempted"] - 1
    assert {"solution_rel_err", "true_residual_gap"} <= _over(res)


def test_iterations_answered_short_are_not_correct(cell):
    mat = cg_solve.inputs(cell.config, SEED, cell.cpu_scale)
    b = cg_solve.rhs(cg_reference.operator(mat), mat.shape[0], SEED, 0, 0.1)
    x, residuals = cg_reference.CgReference(mat).solve(b, 40)
    errors = cg_solve.check(mat, [cg_solve.Solve(b, x, 40, residuals)], cell.traffic)
    worst, failed = run.decide(errors, cell.limits)
    assert failed == 1 and worst["iterations_short"] == 10.0
    assert worst["residual_gap"] == float("inf")  # 40 residuals for 50 asked


# -------------------------------------------------------------- the readers
def _reduction(kernel_ns, xla_ns, calls, busy_ns=600.0, window_ns=1000.0):
    return tracing.Reduction(
        window_ns=window_ns, busy_ns=busy_ns, spmv_calls=calls, kernel_ns=kernel_ns,
        xla_ns=xla_ns, request_ns=900.0, request_idle_ns=300.0, device_ops=[],
        idle_gaps=[], op_ns={}, module_ns={}, devices=1)


@pytest.mark.parametrize("metric, expected", [
    ("kernel_ms.cg", 2e6 / 4 / 1e6),
    ("xla_ms.cg", 18e6 / 4 / 1e6),
    ("spmv_roofline.cg", 100.0 * 156.5e-6 * 1e9 / (20e6 / 4)),
    ("idle_share.cg", 40.0),
])
def test_the_device_readers_read_the_reduced_trace(metric, expected):
    ctx = run.TraceContext(_reduction(2e6, 18e6, 4), 4, 156.5e-6)
    assert run.load_reader(metric)(ctx) == pytest.approx(expected)
    base = metric.replace(".cg", ".solve")
    assert run.load_reader(metric)(ctx) == run.load_reader(base)(ctx)


@pytest.mark.parametrize("metric", ["kernel_ms.cg", "xla_ms.cg", "spmv_roofline.cg"])
def test_the_device_readers_give_nothing_without_spmv_programs(metric):
    ctx = run.TraceContext(_reduction(0.0, 0.0, 0), 4, 156.5e-6)
    assert run.load_reader(metric)(ctx) is None


def _span(id_, name, parent, dur_s, **attrs):
    return {"name": name, "id": id_, "parent": parent, "root": 1, "ts": 0.0,
            "dur_s": dur_s, "thread": 1, "attrs": attrs}


SPANS = [
    _span(2, "session.fingerprint", 1, 0.400, bytes=8, kind="csr"),
    _span(4, "kernel.execute", 3, 0.250, fmt="csr", gather="xla"),
    _span(5, "solver.vector", 3, 0.012),
    _span(3, "solver.iterate", 1, 0.270, iteration=1),
    _span(7, "kernel.execute", 6, 0.250, fmt="csr", gather="xla"),
    _span(8, "solver.vector", 6, 0.010),
    _span(6, "solver.iterate", 1, 0.268, iteration=2),
    _span(1, "solver.solve", None, 1.0),
]


@pytest.mark.parametrize("metric, expected", [
    ("vector_ms.cg", (12 + 10) / 2), ("fingerprint_ms.cg", 400 / 2)])
def test_the_span_readers_sum_their_spans_per_spmv(metric, expected):
    value = run.load_reader(metric).__globals__["value"]
    assert value(SPANS, 0, 2) == pytest.approx(expected)
    assert value(SPANS, 1, 2) is None  # spans dropped: a sum would be short
    assert value(SPANS, 0, 0) is None


def test_vector_ms_gives_nothing_for_a_program_without_the_span():
    value = run.load_reader("vector_ms.cg").__globals__["value"]
    assert value([s for s in SPANS if s["name"] != "solver.vector"], 0, 2) is None


def test_a_traced_run_on_the_cpu_reports_the_span_readers(cell, tuner, monkeypatch):
    """The CPU's trace has no device plane, which the trace reduction
    refuses, so an empty one is added; the span readers do not read it."""
    real_load = tracing.load

    def load_with_a_device(log_dir):
        trace = real_load(log_dir)
        trace.devices.setdefault("/device:TPU:0", {})
        return trace

    monkeypatch.setattr(tracing, "load", load_with_a_device)
    res = run.run_cell(CELL, SEED, 0.3, True, require_chip=False, scale=cell.cpu_scale,
                       tuner=tuner)
    assert res["correct"] is True
    metrics = res["metrics"]
    assert {"vector_ms.cg", "fingerprint_ms.cg", "idle_share.cg"} <= set(metrics)
    assert metrics["vector_ms.cg"]["value"] > 0 and metrics["fingerprint_ms.cg"]["value"] > 0
