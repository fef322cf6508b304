"""Measurement-calibrated cost model: affine fit quality, uncalibrated
fallback identity, persistence round-trip, the block-count flip once launch
overhead is charged, and the session calibrate() loop (telemetry -> fit ->
cache eviction -> persisted sibling artifact)."""

import numpy as np
import pytest

from repro.core.objectives import (
    HARDWARE,
    TPU_V4,
    CalibratedCostModel,
    FormatCalibration,
    MatrixStats,
    TpuCostModel,
)
from repro.core.session import AutoSpmvSession, _calibration_path
from repro.kernels.common import DEFAULT_SCHEDULE
from repro.partition import plan_partitioned
from repro.sparse.generate import random_matrix
from repro.sparse.registry import format_names
from repro.telemetry import TelemetryRecorder

from tests.test_partition import StubPredictor, hetero_matrix, stub_tuner


@pytest.fixture(scope="module")
def stats():
    dense = random_matrix(256, 6.0, "powerlaw", seed=4).astype(np.float32)
    return MatrixStats(dense)


# ----------------------------------------------------------------- fallback


def test_uncalibrated_equals_base_model(stats):
    base, uncal = TpuCostModel(), CalibratedCostModel()
    for fmt in format_names():
        for sched in (DEFAULT_SCHEDULE, DEFAULT_SCHEDULE.replace(unroll=4)):
            assert uncal.evaluate(stats, fmt, sched) == base.evaluate(
                stats, fmt, sched
            )


def test_unknown_format_falls_back(stats):
    cal = CalibratedCostModel(
        corrections={"ell": FormatCalibration(1.0, 2.0, samples=8)}
    )
    assert cal.evaluate(stats, "csr", DEFAULT_SCHEDULE) == TpuCostModel().evaluate(
        stats, "csr", DEFAULT_SCHEDULE
    )


# ---------------------------------------------------------------------- fit


def test_affine_fit_recovers_overhead_and_scale(stats):
    preds = [1e-5 * (1 + i) for i in range(32)]
    samples = {"csr": [(p, 4.0 * p + 3e-4) for p in preds]}
    cal = CalibratedCostModel.fit(samples)
    c = cal.corrections["csr"]
    assert c.latency_scale == pytest.approx(4.0)
    assert c.launch_overhead_s == pytest.approx(3e-4)
    assert c.samples == 32
    base = TpuCostModel().evaluate(stats, "csr", DEFAULT_SCHEDULE)
    corrected = cal.evaluate(stats, "csr", DEFAULT_SCHEDULE)
    assert corrected.latency == pytest.approx(3e-4 + 4.0 * base.latency)
    assert corrected.energy == base.energy  # energy stays modeled


def test_single_sample_is_scale_only():
    cal = CalibratedCostModel.fit({"ell": [(1e-4, 5e-4)]})
    c = cal.corrections["ell"]
    assert c.latency_scale == pytest.approx(5.0)
    assert c.launch_overhead_s == 0.0


def test_degenerate_fit_falls_back_to_rescale():
    # measured DECREASES with predicted: the affine fit would extrapolate
    # negative for small kernels, so the safe pure rescale must win
    pairs = [(1e-5 * (1 + i), 1e-3 / (1 + i)) for i in range(8)]
    cal = CalibratedCostModel.fit({"csr": pairs})
    c = cal.corrections["csr"]
    assert c.launch_overhead_s == 0.0 and c.latency_scale > 0


def test_error_shrinks_as_telemetry_accumulates():
    """Mean relative error vs measured is monotone non-increasing (within
    noise) as synthetic telemetry accumulates: more pairs, better fit."""
    rng = np.random.default_rng(0)
    true_scale, true_overhead = 6.0, 5e-4
    preds = 1e-5 * (1 + rng.random(256) * 40)
    meas = true_overhead + true_scale * preds * (1 + 0.05 * rng.standard_normal(256))

    def mre(n):
        cal = CalibratedCostModel.fit({"csr": list(zip(preds[:n], meas[:n]))})
        c = cal.corrections["csr"]
        fitted = c.launch_overhead_s + c.latency_scale * preds
        return float(np.mean(np.abs(fitted - meas) / meas))

    errs = [mre(n) for n in (2, 8, 32, 256)]
    assert errs[-1] <= errs[0]
    # and the calibrated model beats the raw model by far more than 2x
    raw_err = float(np.mean(np.abs(preds - meas) / meas))
    assert errs[-1] <= raw_err / 2


# ------------------------------------------------------------- persistence


def test_save_load_round_trip(tmp_path):
    cal = CalibratedCostModel.fit(
        {"csr": [(1e-5, 2e-4), (2e-5, 3e-4)], "sell": [(1e-5, 9e-5)]},
        hw=TPU_V4,
    )
    path = tmp_path / "cal.json"
    cal.save(path)
    loaded = CalibratedCostModel.load(path)
    assert loaded.hw is HARDWARE["tpu_v4"]
    assert loaded.corrections == cal.corrections
    with pytest.raises(ValueError):
        path.write_text('{"version": 99}')
        CalibratedCostModel.load(path)


def test_load_refuses_unknown_hardware(tmp_path):
    """A calibration saved for hardware this build does not know must not
    silently be applied to another chip's model."""
    path = tmp_path / "cal.json"
    CalibratedCostModel.fit({"csr": [(1e-5, 2e-4)]}, hw=TPU_V4).save(path)
    raw = path.read_text().replace('"tpu_v4"', '"tpu_v99"')
    path.write_text(raw)
    with pytest.raises(ValueError, match="unknown hardware 'tpu_v99'"):
        CalibratedCostModel.load(path)
    assert CalibratedCostModel.load(path, hw=TPU_V4).hw is TPU_V4


# --------------------------------------------------------------- plan flip


def test_block_count_flips_once_launch_overhead_is_calibrated():
    """The uncalibrated planner partitions the hetero matrix; charging a
    large measured per-launch cost makes k launches lose to one."""
    dense = hetero_matrix()
    uncal = plan_partitioned(StubPredictor(), dense, "latency")
    assert uncal.partitioned and uncal.n_blocks > 1

    overhead = 10.0 * uncal.monolithic.latency
    cal = CalibratedCostModel(
        corrections={
            f: FormatCalibration(launch_overhead_s=overhead, samples=4)
            for f in format_names()
        }
    )
    flipped = plan_partitioned(StubPredictor(), dense, "latency", cost_model=cal)
    assert not flipped.partitioned and flipped.n_blocks == 1


# ------------------------------------------------------------------ session


def test_session_calibrate_closes_the_loop(tmp_path):
    cache_path = tmp_path / "tuning.json"
    session = AutoSpmvSession(
        stub_tuner(), cache_path=cache_path, telemetry=TelemetryRecorder()
    )
    assert session.cost_model is None
    dense = hetero_matrix()
    res = session.partitioned_optimize(dense, "latency")
    assert res.n_blocks > 1

    # telemetry says every block really costs a large fixed launch overhead
    overhead = 10.0 * res.plan.monolithic.latency
    for bp in res.plan.blocks:
        pred = max(bp.modeled.latency, 1e-7)
        for rep in range(3):
            session.telemetry.observe(
                bucket=f"b{bp.block.index}",
                objective="latency",
                fmt=bp.fmt,
                measured_s=overhead + pred * (1 + 0.01 * rep),
                predicted_s=pred,
            )

    model = session.calibrate()
    assert session.cost_model is model and model.corrections
    # the stale composite plan was evicted: the next request re-plans with
    # the calibrated model and stops fantasizing that launches are free
    assert session.cache.peek(res.bucket, "latency", res.mode) is None
    res2 = session.partitioned_optimize(dense, "latency")
    assert not res2.cache_hit
    assert res2.n_blocks == 1

    # persisted next to the cache; a restarted session auto-loads it
    cal_path = _calibration_path(cache_path)
    assert cal_path.exists()
    session.save()
    warm = AutoSpmvSession(stub_tuner(), cache_path=cache_path)
    assert warm.cost_model is not None
    assert warm.cost_model.corrections.keys() == model.corrections.keys()


def test_session_calibrate_requires_telemetry():
    session = AutoSpmvSession(stub_tuner())
    with pytest.raises(ValueError):
        session.calibrate()
