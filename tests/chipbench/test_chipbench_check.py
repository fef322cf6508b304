"""What decides ``correct``: the control fails, and so does a broken run.

The power-iteration cells run here at a small size on the CPU (the Pallas
kernels in interpret mode), with a small tuner, through the same
``run_cell`` the chip runs, minus its look for a chip. Every cell's control
is read at the size its limits file gives as ``cpu_scale``.
"""

import json

import numpy as np
import pytest

from chipbench import matrices, readings, reference, run
from chipbench.drivers import power_solve

SPEC = json.loads((run.ROOT / "BENCHMARK.json").read_text())
ALL_CELLS = sorted(w["name"] for w in SPEC["workloads"])
SCALES = {name: run.load_cell(name).cpu_scale for name in ALL_CELLS}
# the cells that this file's faults can be planted in
CELLS = [name for name in ALL_CELLS
         if run.load_cell(name).traffic["driver"] == "power_solve"]
SEED = 2**31 + 77


@pytest.fixture(scope="module")
def tuner():
    return power_solve.build_tuner({"tuner": {"scale": 0.0008, "train_matrices": 2}})


def _run(cell, tuner):
    return run.run_cell(cell, SEED, 0.3, False, require_chip=False, scale=SCALES[cell],
                        tuner=tuner)


def _over(res):
    return {k for k, c in res["checks"].items() if c["value"] > c["limit"]}


@pytest.mark.parametrize("cell", CELLS)
def test_a_sound_run_is_correct(cell, tuner):
    res = _run(cell, tuner)
    assert res["correct"] is True
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == {"vector_rel_err", "eigenvalue_rel_err", "residual_gap",
                                  "iterations_short"}
    assert not _over(res)
    assert set(res["metrics"]) == {"spmv_ms", "tune_s", "setup_s"}


def _control_request(self, i):
    # the bfloat16 reference answers in the program's place
    if not hasattr(self, "_control"):
        self._control = reference.PowerReference(self.dense, precision="bfloat16")
    x0 = matrices.start_vector(self.n_rows, self.seed, i)
    iters = self.traffic["max_iters"]
    vector, eigenvalue, residuals = self._control.solve(x0, iters)
    return power_solve.Solve(x0, vector, eigenvalue, iters, residuals)


@pytest.mark.parametrize("cell", CELLS)
def test_the_bfloat16_control_in_the_programs_place_is_not_correct(cell, tuner, monkeypatch):
    monkeypatch.setattr(power_solve.Program, "request", _control_request)
    res = _run(cell, tuner)
    assert res["correct"] is False
    assert res["failed"] == res["attempted"] >= 1
    assert {"vector_rel_err", "residual_gap"} <= _over(res)


@pytest.mark.parametrize("cell", ALL_CELLS)
def test_the_control_readings_are_not_correct(cell):
    c = run.load_cell(cell)
    got = readings.control_readings(c, SEED, scale=SCALES[cell])
    assert got["correct"] is False
    assert any(got[k] > lim for k, lim in c.limits.items())
    if cell in CELLS:
        assert got["vector_rel_err"] > c.limits["vector_rel_err"]
        assert got["residual_gap"] > c.limits["residual_gap"]


def test_the_reference_in_its_own_place_reads_zero():
    c = run.load_cell("rim.solve")
    dense = matrices.generate(c.config["matrix"], SEED, SCALES["rim.solve"])
    exact = reference.PowerReference(dense)
    x0 = matrices.start_vector(dense.shape[0], SEED, 0)
    vec, lam, res = exact.solve(x0, 50)
    assert reference.compare(x0, vec, lam, res, 50, 50, exact) == {
        "vector_rel_err": 0.0, "eigenvalue_rel_err": 0.0, "residual_gap": 0.0,
        "iterations_short": 0.0}


# ---------------------------------------------------------------- faults
def _altered(y, x, prev):
    # a wrong answer where the SpMV produces it: one entry of y off by half
    return y.at[0].multiply(1.5)


def _unchanged(y, x, prev):
    # a step that returns its state unchanged: y = x
    return x


def _stale(y, x, prev):
    # the previous call's answer returned again
    return prev


class _Planted:
    """Wraps every SpMV call; plants ``fault`` at the ``at``-th call of the
    window's first request, or at every call where ``at`` is None."""

    def __init__(self, fault, at):
        self.fault, self.at = fault, at
        self.calls, self.prev, self.fired = None, None, 0

    def request(self, original):
        def request(program, i):
            self.calls = 0 if i == 0 else None
            return original(program, i)
        return request

    def call(self, original):
        def call(kernel, x):
            y = original(kernel, x)
            prev, self.prev = self.prev, y
            if self.calls is not None:
                self.calls += 1
            if self.at is None or self.calls == self.at:
                self.fired += 1
                return self.fault(y, x, y if prev is None else prev)
            return y
        return call


def _plant(monkeypatch, fault, at):
    from repro.kernels.ops import PreparedSpmv

    planted = _Planted(fault, at)
    monkeypatch.setattr(PreparedSpmv, "__call__", planted.call(PreparedSpmv.__call__))
    monkeypatch.setattr(power_solve.Program, "request",
                        planted.request(power_solve.Program.request))
    return planted


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", [_altered, _unchanged], ids=["answer_altered", "state_unchanged"])
def test_a_fault_at_every_call_is_not_correct(cell, fault, tuner, monkeypatch):
    _plant(monkeypatch, fault, None)
    res = _run(cell, tuner)
    assert res["correct"] is False
    assert res["failed"] == res["attempted"] >= 1
    assert _over(res)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault, at", [(_unchanged, 3), (_stale, 3), (_altered, 10)],
                         ids=["state_unchanged_at_3", "stale_answer_at_3", "answer_altered_at_10"])
def test_a_fault_in_one_iteration_is_not_correct(cell, fault, at, tuner, monkeypatch):
    """One faulty SpMV in the whole window: the residual of that iteration
    departs from the reference's, whatever the final vector shows."""
    planted = _plant(monkeypatch, fault, at)
    res = _run(cell, tuner)
    assert planted.fired == 1
    assert res["correct"] is False
    assert res["failed"] == 1
    assert "residual_gap" in _over(res)


def test_reference_follows_the_stated_iteration():
    """Normalized power iteration on a 2 x 2 matrix with a known answer."""
    dense = np.array([[2.0, 0.0], [0.0, 1.0]], np.float32)
    vec, lam, res = reference.PowerReference(dense).solve(np.array([1.0, 1.0]), 60)
    assert lam == pytest.approx(2.0)
    assert vec == pytest.approx([1.0, 0.0], abs=1e-7)
    # first iteration: x = (1, 1)/sqrt 2, y = (2, 1)/sqrt 2, lambda = 3/2
    assert res[0] == pytest.approx(np.linalg.norm([0.5, -0.5]) / np.sqrt(2) / 1.5)
    assert len(res) == 60 and res[-1] < 1e-15


def test_residual_histories_of_different_length_never_pass():
    assert reference.residual_gap([0.1, 0.01], [0.1, 0.01, 0.001]) == float("inf")
    assert reference.residual_gap([0.1, 0.02], [0.1, 0.01]) == pytest.approx(1.0)
    # under the floor the gap is taken against the floor, not the reference
    floor = reference.RESIDUAL_FLOOR
    assert reference.residual_gap([1.0, floor / 10], [1.0, floor / 1e9]) == pytest.approx(0.1)


def test_an_unknown_precision_raises():
    with pytest.raises(ValueError, match="unknown precision"):
        reference.PowerReference(np.eye(2, dtype=np.float32), precision="int4")


def test_the_compile_counter_sees_a_new_program():
    import jax
    import jax.numpy as jnp

    counter = run.CompileCounter()
    x = jnp.arange(7.0)
    jax.monitoring.register_event_duration_secs_listener(counter)
    try:
        counter.active = True
        jax.jit(lambda v: v * 3 + 1)(x).block_until_ready()
        seen = dict(counter.events)
        counter.active = False
        jax.jit(lambda v: v * 5 + 2)(x).block_until_ready()
    finally:
        jax.monitoring.unregister_event_duration_listener(counter)
    assert seen.get("/jax/core/compile/backend_compile_duration", 0) >= 1
    assert counter.events == seen
