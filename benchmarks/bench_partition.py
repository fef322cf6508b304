"""Partitioned heterogeneous-format SpMV vs the best monolithic plan.

The acceptance study for ``repro.partition``: on a synthetic
block-heterogeneous matrix (a dense-band block stacked on a power-law block,
both from ``sparse/generate.py``) the composite plan must beat the best
single-format monolithic plan on the modeled latency objective, and a
homogeneous matrix must fall back to block count 1. Reports the modeled
margin, the measured (interpret-mode wall time) margin, per-block routing,
and a multi-device ``shard_map`` correctness pass on however many devices
the host exposes.

A calibration study rides along: per-block (predicted, measured) pairs
from ``timed_call`` feed ``CalibratedCostModel.fit``; its mean relative
error against the same measurements must be at most half the uncalibrated
model's.
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks.common import print_table, save_result
from repro.core.objectives import CalibratedCostModel
from repro.core.session import build_tuner
from repro.kernels.ops import compile_spmv
from repro.partition import compile_partitioned, partition_rows, shard_partitioned
from repro.telemetry import TelemetryRecorder
from repro.sparse.generate import MATRIX_NAMES, random_matrix
from repro.utils.logging import get_logger

log = get_logger("bench.partition")

SCALES = {
    "smoke": dict(n=512, band_avg=128.0, tail_avg=3.0, train_scale=0.0008,
                  train_names=4, reps=1),
    "ci": dict(n=512, band_avg=128.0, tail_avg=3.0, train_scale=0.0012,
               train_names=8, reps=2),
    "paper": dict(n=1024, band_avg=192.0, tail_avg=4.0, train_scale=0.0015,
                  train_names=12, reps=3),
}


def heterogeneous_matrix(n: int, band_avg: float, tail_avg: float) -> np.ndarray:
    """Dense-band top half stacked on a power-law bottom half."""
    top = random_matrix(n, band_avg, "denseband", seed=1)[: n // 2]
    bot = random_matrix(n, tail_avg, "powerlaw", seed=2)[n // 2 :]
    return np.vstack([top, bot]).astype(np.float32)


def _measure(kernel, x, reps: int) -> tuple[float, np.ndarray]:
    y = np.asarray(kernel(x))  # warmup (traces + compiles)
    t0 = time.perf_counter()
    for _ in range(max(reps, 1)):
        y = np.asarray(kernel(x))
    return (time.perf_counter() - t0) / max(reps, 1), y


def run(scale: str = "ci") -> dict:
    cfg = SCALES.get(scale, SCALES["ci"])
    n, reps = cfg["n"], cfg["reps"]
    tuner = build_tuner(
        scale=cfg["train_scale"],
        names=MATRIX_NAMES[: cfg["train_names"]],
        n_extra=0,
        fit_overhead=False,
    )
    rng = np.random.default_rng(0)
    out: dict = {"scale": scale, "n": n}

    # --- heterogeneous: composite plan must beat the best monolithic ------
    het = heterogeneous_matrix(n, cfg["band_avg"], cfg["tail_avg"])
    x = rng.normal(size=n).astype(np.float32)
    ref = het @ x
    plan = tuner.plan_partitioned(het, "latency")
    margin_pct = 100.0 * plan.gain()
    out["hetero"] = {
        "n_blocks": plan.n_blocks,
        "formats": list(plan.formats),
        "monolithic_fmt": plan.monolithic_fmt,
        "modeled_latency_s": plan.modeled.latency,
        "monolithic_latency_s": plan.monolithic.latency,
        "modeled_margin_pct": margin_pct,
    }
    assert plan.partitioned, (
        f"heterogeneous matrix failed to partition: k={plan.n_blocks}"
    )
    assert margin_pct > 0, f"partitioned plan lost by {margin_pct:.1f}%"

    part_kernel = compile_partitioned(het, plan)
    mono_kernel = compile_spmv(
        het, plan.monolithic_fmt, plan.monolithic_schedule or plan.blocks[0].schedule
    )
    t_part, y_part = _measure(part_kernel, x, reps)
    t_mono, y_mono = _measure(mono_kernel, x, reps)
    norm = np.abs(ref).max() + 1e-9
    err_part = float(np.abs(y_part - ref).max() / norm)
    err_mono = float(np.abs(y_mono - ref).max() / norm)
    assert err_part < 2e-2, f"partitioned output diverged: {err_part}"
    out["hetero"].update(
        measured_partitioned_s=t_part,
        measured_monolithic_s=t_mono,
        rel_err_partitioned=err_part,
        rel_err_monolithic=err_mono,
    )

    # --- calibration: measured block times halve the model's error --------
    recorder = TelemetryRecorder()
    for _ in range(max(reps, 3)):
        _, block_times = part_kernel.timed_call(x)
        for bp, t in zip(plan.blocks, block_times):
            recorder.observe(
                bucket=f"blk{bp.block.index}",
                objective="latency",
                fmt=bp.fmt,
                measured_s=t,
                predicted_s=max(bp.modeled.latency, 1e-9),
            )
    cal = CalibratedCostModel.fit_from_telemetry(recorder)
    errs_raw, errs_cal = [], []
    for fmt, pairs in recorder.calibration_samples().items():
        c = cal.corrections.get(fmt)
        for pred, meas in pairs:
            errs_raw.append(abs(pred - meas) / meas)
            fitted = c.launch_overhead_s + c.latency_scale * pred if c else pred
            errs_cal.append(abs(fitted - meas) / meas)
    mre_raw = float(np.mean(errs_raw))
    mre_cal = float(np.mean(errs_cal))
    # a calibrated re-plan now charges the measured per-launch fixed cost
    plan_cal = tuner.plan_partitioned(het, "latency", cost_model=cal)
    out["calibration"] = {
        "samples": sum(len(p) for p in recorder.calibration_samples().values()),
        "formats_fitted": len(cal.corrections),
        "mre_uncalibrated": mre_raw,
        "mre_calibrated": mre_cal,
        "calibrated_n_blocks": plan_cal.n_blocks,
    }
    assert mre_cal <= mre_raw / 2, (
        f"calibration did not halve the model error: "
        f"{mre_cal:.3f} vs raw {mre_raw:.3f}"
    )

    # --- homogeneous: must fall back to the monolithic plan ---------------
    homo = random_matrix(n, 12.0, "powerlaw", seed=5).astype(np.float32)
    plan_h = tuner.plan_partitioned(homo, "latency")
    out["homogeneous"] = {
        "n_blocks": plan_h.n_blocks,
        "formats": list(plan_h.formats),
        "modeled_latency_s": plan_h.modeled.latency,
        "monolithic_latency_s": plan_h.monolithic.latency,
    }
    assert not plan_h.partitioned, (
        f"homogeneous matrix partitioned: k={plan_h.n_blocks}"
    )
    assert plan_h.modeled.latency <= plan_h.monolithic.latency * (1 + 1e-9), (
        "monolithic fallback regressed its own baseline"
    )

    # --- multi-device shard_map path (however many devices exist) ---------
    import jax

    n_dev = len(jax.devices())
    sharded = shard_partitioned(het, partition_rows(het, n_dev))
    y_sh = sharded(x)
    err_sh = float(np.abs(y_sh - ref).max() / norm)
    assert err_sh < 2e-2, f"sharded output diverged: {err_sh}"
    out["sharded"] = {"devices": n_dev, "rel_err": err_sh}

    print_table(
        "Partitioned vs monolithic (modeled latency objective)",
        ["matrix", "k", "formats", "modeled_s", "mono_s", "margin %"],
        [
            ["hetero", plan.n_blocks, "+".join(plan.formats),
             plan.modeled.latency, plan.monolithic.latency, margin_pct],
            ["homo", plan_h.n_blocks, "+".join(set(plan_h.formats)),
             plan_h.modeled.latency, plan_h.monolithic.latency,
             100.0 * plan_h.gain()],
        ],
    )
    log.info(
        "hetero: measured %.2f ms partitioned vs %.2f ms monolithic "
        "(interpret mode); sharded over %d device(s), rel err %.2e",
        t_part * 1e3,
        t_mono * 1e3,
        n_dev,
        err_sh,
    )
    log.info(
        "calibration: %d per-block samples, mean rel err %.2f uncalibrated "
        "-> %.2f calibrated; calibrated planner picks k=%d",
        out["calibration"]["samples"],
        mre_raw,
        mre_cal,
        plan_cal.n_blocks,
    )
    save_result("bench_partition", out)
    return out


if __name__ == "__main__":
    run("ci")
