"""Partitioned heterogeneous-format SpMV: partitioner invariants and edge
cases, composite planning (hetero win + homogeneous monolithic fallback),
exactness of the concatenated executor output against the dense float64
product across formats / partitions / dtypes, the ``timed_call`` warmup fix
(measurement-poisoning regression), and the session / cache / telemetry
integration."""

import dataclasses

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # conftest installs the fallback stub
    from hypothesis import given, settings, strategies as st  # noqa: F811

from repro.core.autotuner import AutoSpMV
from repro.core.features import row_nnz_counts
from repro.core.objectives import ObjectiveValues
from repro.core.session import AutoSpmvSession
from repro.kernels.common import DEFAULT_SCHEDULE
from repro.partition import (
    CompositePlan,
    PartitionedSpmv,
    compile_partitioned,
    partition_rows,
    plan_partitioned,
)
from repro.partition.plan import BlockPlan
from repro.sparse.generate import random_matrix
from repro.sparse.registry import format_names
from repro.telemetry import AdaptiveFormatSelector, TelemetryRecorder, block_arm_bucket


class StubPredictor:
    """Deterministic predictor: fixed format + the default schedule, so plan
    tests exercise the partition/cost-model logic, not classifier fitting."""

    def __init__(self, fmt: str = "csr"):
        self.fmt = fmt

    def predict_format(self, feats, objective):
        return self.fmt

    def predict_schedule(self, feats, objective):
        return DEFAULT_SCHEDULE


def stub_tuner() -> AutoSpMV:
    return AutoSpMV(predictor=StubPredictor())


def hetero_matrix(n: int = 512) -> np.ndarray:
    top = random_matrix(n, n // 4, "denseband", seed=1)[: n // 2]
    bot = random_matrix(n, 3.0, "powerlaw", seed=2)[n // 2 :]
    return np.vstack([top, bot]).astype(np.float32)


# ---------------------------------------------------------------- partitioner


def _check_invariants(part, n_rows):
    assert part.blocks[0].row_start == 0
    assert part.blocks[-1].row_end == n_rows
    for a, b in zip(part.blocks, part.blocks[1:]):
        assert a.row_end == b.row_start
    if n_rows:
        assert all(b.n_rows >= 1 for b in part.blocks)


@pytest.mark.parametrize("pattern", ["banded", "powerlaw", "denseband"])
@pytest.mark.parametrize("k", [1, 2, 4, 8])
def test_partition_covers_rows(pattern, k):
    dense = random_matrix(160, 6.0, pattern, seed=7).astype(np.float32)
    part = partition_rows(dense, k)
    _check_invariants(part, 160)
    assert part.n_blocks == k
    assert part.nnz == int((dense != 0).sum())
    # per-block features describe exactly that block's rows
    counts = row_nnz_counts(dense)
    for b in part.blocks:
        assert b.features.n == b.n_rows
        assert b.nnz == int(counts[b.row_start : b.row_end].sum())


def test_partition_empty_matrix():
    part = partition_rows(np.zeros((0, 8), np.float32), 4)
    assert part.n_blocks == 1
    assert part.blocks[0].row_start == part.blocks[0].row_end == 0
    assert part.nnz == 0


def test_partition_all_empty_rows():
    part = partition_rows(np.zeros((40, 40), np.float32), 4)
    _check_invariants(part, 40)
    assert part.n_blocks == 4
    # even row split when there is no nnz signal
    assert max(b.n_rows for b in part.blocks) <= 2 * (40 // 4)


def test_partition_all_nnz_in_one_row():
    dense = np.zeros((32, 32), np.float32)
    dense[11, :] = 1.0
    part = partition_rows(dense, 4)
    _check_invariants(part, 32)
    # exactly one block owns every nonzero
    assert sorted(b.nnz for b in part.blocks) == [0, 0, 0, 32]


def test_partition_more_blocks_than_rows():
    part = partition_rows(np.eye(3, dtype=np.float32), 8)
    _check_invariants(part, 3)
    assert part.n_blocks == 3  # clamped: a block must own at least one row
    with pytest.raises(ValueError):
        partition_rows(np.eye(3, dtype=np.float32), 0)


def test_partition_balances_nnz():
    dense = random_matrix(256, 8.0, "banded", seed=3).astype(np.float32)
    part = partition_rows(dense, 4)
    assert part.imbalance() < 1.5  # near-even nnz split on uniform rows


def test_refinement_does_not_worsen_balance():
    for seed in range(4):
        dense = random_matrix(192, 6.0, "powerlaw", seed=seed).astype(np.float32)
        raw = partition_rows(dense, 4, refine=False)
        refined = partition_rows(dense, 4, refine=True)
        assert refined.imbalance() <= raw.imbalance() + 1e-9


@given(st.integers(min_value=0, max_value=60), st.integers(min_value=1, max_value=9),
       st.integers(min_value=0, max_value=4))
@settings(max_examples=20, deadline=None)
def test_partition_invariants_property(n_rows, k, seed):
    rng = np.random.default_rng(seed)
    dense = (rng.random((n_rows, 12)) < 0.2).astype(np.float32)
    part = partition_rows(dense, k)
    _check_invariants(part, n_rows)
    assert part.n_blocks == (min(k, n_rows) if n_rows else 1)
    assert part.nnz == int(dense.sum())


# ----------------------------------------------------------------------- plan


def test_plan_heterogeneous_beats_monolithic():
    plan = plan_partitioned(StubPredictor(), hetero_matrix(), "latency")
    assert plan.partitioned and plan.n_blocks > 1
    assert plan.gain() > 0
    assert plan.modeled.latency < plan.monolithic.latency


def test_plan_homogeneous_falls_back_to_monolithic():
    homo = random_matrix(256, 8.0, "powerlaw", seed=5).astype(np.float32)
    plan = plan_partitioned(StubPredictor(), homo, "latency")
    assert not plan.partitioned and plan.n_blocks == 1
    # the fallback IS the best single-format baseline: zero regression
    assert plan.modeled.latency == plan.monolithic.latency
    assert plan.formats == (plan.monolithic_fmt,)


def test_plan_respects_block_count_budget():
    plan = plan_partitioned(
        StubPredictor(), hetero_matrix(), "latency", block_counts=(1, 2)
    )
    assert plan.n_blocks <= 2


# ------------------------------------------------------------------- executor


_ZERO = ObjectiveValues(0.0, 0.0, 0.0, 0.0)


def forced_plan(
    dense: np.ndarray,
    fmts: list[str],
    k: int = 3,
    schedule=DEFAULT_SCHEDULE,
) -> CompositePlan:
    """A CompositePlan with formats assigned round-robin over ``k`` blocks —
    executor tests force the routing so they exercise execution, not
    planning."""
    part = partition_rows(dense, k)
    blocks = tuple(
        BlockPlan(b, fmts[i % len(fmts)], schedule, _ZERO, fmts[i % len(fmts)])
        for i, b in enumerate(part.blocks)
    )
    return CompositePlan("latency", part, blocks, _ZERO, _ZERO, fmts[0], schedule)


def _assert_matches_dense(dense, plan, x, atol_scale=2e-3) -> PartitionedSpmv:
    """Compile ``plan`` and check its output against the float64 product."""
    ref = dense.astype(np.float64) @ x.astype(np.float64)
    kernel = compile_partitioned(dense, plan)
    y = np.asarray(kernel(x))
    assert y.shape == ref.shape
    np.testing.assert_allclose(
        y, ref, rtol=0, atol=atol_scale * max(np.abs(ref).max(), 1e-6)
    )
    return kernel


@pytest.mark.parametrize("fmt", format_names())
@pytest.mark.parametrize("pattern", ["fem", "powerlaw"])
def test_partitioned_output_matches_dense_reference(fmt, pattern, rng):
    """Concatenated per-block output == dense reference, for every
    registered format (heterogeneity cannot corrupt row ranges)."""
    dense = random_matrix(160, 6.0, pattern, seed=11).astype(np.float32)
    x = rng.normal(size=dense.shape[1]).astype(np.float32)
    ref = dense @ x
    kernel = compile_partitioned(dense, forced_plan(dense, [fmt]))
    y = np.asarray(kernel(x))
    assert y.shape == ref.shape
    np.testing.assert_allclose(y, ref, rtol=0, atol=2e-3 * np.abs(ref).max())


def test_mixed_formats_exactness(rng):
    dense = hetero_matrix(256)
    fmts = ["csr", "ell", "bell", "sell"]
    plan = forced_plan(dense, fmts, 4)
    part = plan.partition
    kernel = compile_partitioned(dense, plan)
    assert kernel.formats == tuple(fmts[: part.n_blocks])
    x = rng.normal(size=dense.shape[1]).astype(np.float32)
    ref = dense @ x
    y, times = kernel.timed_call(x)
    np.testing.assert_allclose(y, ref, rtol=0, atol=2e-3 * np.abs(ref).max())
    # ordinal/shape checks only — no wall-clock thresholds (CI runners are
    # arbitrarily loaded); timing *quality* is covered by
    # test_timed_call_warms_up_before_measuring
    assert len(times) == part.n_blocks and all(t > 0 for t in times)


@pytest.mark.parametrize("fmt", format_names())
@pytest.mark.parametrize("k", [1, 2, 4])
def test_partitioned_matches_dense_per_format_and_block_count(fmt, k, rng):
    dense = random_matrix(160, 6.0, "powerlaw", seed=11).astype(np.float32)
    x = rng.normal(size=dense.shape[1]).astype(np.float32)
    kernel = _assert_matches_dense(dense, forced_plan(dense, [fmt], k), x)
    assert kernel.n_blocks == k
    assert kernel.formats == (fmt,) * k


@pytest.mark.parametrize(
    "fmts",
    [["csr", "ell"], ["sell", "bell"], ["csr", "ell", "bell", "sell"]],
)
def test_partitioned_heterogeneous_formats(fmts, rng):
    dense = hetero_matrix(256)
    x = rng.normal(size=dense.shape[1]).astype(np.float32)
    kernel = _assert_matches_dense(dense, forced_plan(dense, fmts, 4), x)
    assert kernel.n_blocks == 4
    assert set(kernel.formats) == set(fmts)


def test_partitioned_bf16_accumulation(rng):
    dense = random_matrix(192, 5.0, "banded", seed=3).astype(np.float32)
    x = rng.normal(size=dense.shape[1]).astype(np.float32)
    sched = DEFAULT_SCHEDULE.replace(accum_dtype="bfloat16")
    kernel = _assert_matches_dense(
        dense, forced_plan(dense, ["csr", "ell"], 2, sched), x, atol_scale=2e-2
    )
    assert {b.kernel.schedule.accum_dtype for b in kernel.blocks} == {"bfloat16"}


def test_partitioned_single_block_plan(rng):
    dense = random_matrix(96, 4.0, "fem", seed=9).astype(np.float32)
    x = rng.normal(size=dense.shape[1]).astype(np.float32)
    kernel = _assert_matches_dense(dense, forced_plan(dense, ["csr"], 1), x)
    assert kernel.n_blocks == 1


def test_partitioned_all_zero_matrix():
    dense = np.zeros((64, 64), np.float32)
    kernel = compile_partitioned(dense, forced_plan(dense, ["csr"], 2))
    y = np.asarray(kernel(np.ones(64, np.float32)))
    assert y.shape == (64,) and not y.any()
    assert kernel.n_blocks == 2


def test_partitioned_empty_block_contributes_zero_rows(rng):
    # one populated row: the nnz balancer leaves the other blocks empty,
    # and their rows of y must come out exactly zero
    dense = np.zeros((64, 64), np.float32)
    dense[11] = rng.normal(size=64).astype(np.float32)
    plan = forced_plan(dense, ["csr"], 4)
    assert any(bp.block.nnz == 0 for bp in plan.blocks)
    x = rng.normal(size=64).astype(np.float32)
    kernel = _assert_matches_dense(dense, plan, x)
    y = np.asarray(kernel(x))
    for bp in plan.blocks:
        if bp.block.nnz == 0:
            assert not y[bp.block.row_start : bp.block.row_end].any()


def test_partitioned_single_hub_row(rng):
    dense = np.zeros((48, 48), np.float32)
    dense[17] = rng.normal(size=48).astype(np.float32)
    x = rng.normal(size=48).astype(np.float32)
    _assert_matches_dense(dense, forced_plan(dense, ["csr", "ell"], 4), x)


@given(
    st.integers(min_value=1, max_value=48),
    st.integers(min_value=1, max_value=5),
    st.sampled_from(["csr", "ell", "bell", "sell"]),
    st.sampled_from(["csr", "ell", "bell", "sell"]),
    st.integers(min_value=0, max_value=6),
)
@settings(max_examples=25, deadline=None)
def test_partitioned_matches_dense_property(n_rows, k, fmt_a, fmt_b, seed):
    rng = np.random.default_rng(seed)
    dense = (rng.random((n_rows, 64)) < 0.15).astype(np.float32)
    dense *= rng.normal(size=dense.shape).astype(np.float32)
    x = rng.normal(size=64).astype(np.float32)
    _assert_matches_dense(dense, forced_plan(dense, [fmt_a, fmt_b], k), x)


# ------------------------------------------------- timed_call measurement


def test_timed_call_warms_up_before_measuring(rng):
    """Regression: the first measured window must not include trace/compile
    (it used to seed bandit arms with launch-setup garbage)."""
    dense = hetero_matrix(256)
    plan = forced_plan(dense, ["csr", "ell"], 2)
    kernel = compile_partitioned(dense, plan)
    calls = []

    def counting(f, idx):
        def run(x):
            calls.append(idx)
            return f(x)

        return run

    kernel.blocks = [
        dataclasses.replace(b, kernel=counting(b.kernel, i))
        for i, b in enumerate(kernel.blocks)
    ]
    x = rng.normal(size=dense.shape[1]).astype(np.float32)
    y, times = kernel.timed_call(x)
    # first timed_call: one untimed warmup + one timed execution per block
    assert sorted(calls) == [0, 0, 1, 1]
    assert len(times) == 2 and all(t > 0 for t in times)
    np.testing.assert_allclose(
        y, dense @ x, rtol=0, atol=2e-3 * np.abs(dense @ x).max()
    )
    calls.clear()
    kernel.timed_call(x)
    assert sorted(calls) == [0, 1]  # warmed: no extra executions

    fresh = compile_partitioned(dense, plan)
    first = fresh.timed_call(x)[1]
    steady = [fresh.timed_call(x)[1] for _ in range(4)]
    med = np.median([t for ts in steady for t in ts])
    # interpret-mode sanity: the first recorded sample sits within a sane
    # multiple of steady state rather than orders of magnitude above it
    assert max(first) <= 50 * max(med, 1e-5)


def test_timed_call_opt_out_keeps_cold_measurement(rng):
    dense = hetero_matrix(128)
    kernel = compile_partitioned(dense, forced_plan(dense, ["csr"], 2))
    calls = []

    def counting(f, idx):
        def run(x):
            calls.append(idx)
            return f(x)

        return run

    kernel.blocks = [
        dataclasses.replace(b, kernel=counting(b.kernel, i))
        for i, b in enumerate(kernel.blocks)
    ]
    x = rng.normal(size=dense.shape[1]).astype(np.float32)
    kernel.timed_call(x, warmup=False)
    assert sorted(calls) == [0, 1]  # no warmup executions


# -------------------------------------------------------------------- session


def test_session_partitioned_cache_roundtrip(tmp_path):
    dense = hetero_matrix()
    x = np.random.default_rng(0).normal(size=dense.shape[1]).astype(np.float32)
    ref = dense @ x
    session = AutoSpmvSession(stub_tuner())
    r1 = session.partitioned_optimize(dense, "latency")
    assert not r1.cache_hit and session.stats.plans_computed == 1
    np.testing.assert_allclose(
        np.asarray(r1.kernel(x)), ref, rtol=0, atol=2e-2 * np.abs(ref).max()
    )
    r2 = session.partitioned_optimize(dense, "latency")
    assert r2.cache_hit and session.stats.plans_computed == 1
    assert r2.plan.formats == r1.plan.formats

    # JSON round-trip: a fresh session replays the composite decisions
    path = session.save(tmp_path / "cache.json")
    from repro.core.cache import TuningCache

    warm = AutoSpmvSession(stub_tuner(), cache=TuningCache.load(path))
    r3 = warm.partitioned_optimize(dense, "latency")
    assert r3.cache_hit and warm.stats.plans_computed == 0
    assert r3.plan.formats == r1.plan.formats
    assert r3.n_blocks == r1.n_blocks


def test_session_partition_mode_keyed_by_budget():
    dense = hetero_matrix()
    session = AutoSpmvSession(stub_tuner())
    r8 = session.partitioned_optimize(dense, "latency", max_blocks=8)
    r2 = session.partitioned_optimize(dense, "latency", max_blocks=2)
    assert r8.mode != r2.mode  # budgets must not alias cache entries
    assert r2.n_blocks <= 2


def test_serve_partitioned_reports_per_block_identity():
    dense = hetero_matrix()
    session = AutoSpmvSession(
        stub_tuner(),
        telemetry=TelemetryRecorder(),
        adaptive=AdaptiveFormatSelector(),
    )
    res = session.serve_partitioned(dense, "latency")
    k = res.n_blocks
    assert k > 1
    assert len(res.formats) == k and len(res.exploratory) == k
    x = np.random.default_rng(0).normal(size=dense.shape[1]).astype(np.float32)
    y, times = res.kernel.timed_call(x)
    assert len(times) == k and all(t > 0 for t in times)
    # feed SYNTHETIC per-block times: the arm bookkeeping under test is
    # independent of this runner's wall clock, so the assertions stay
    # deterministic on loaded CI machines
    session.observe_partitioned(res, [0.01] * k)
    assert session.stats.observations == 1
    # one telemetry/bandit cell per block, keyed by block_arm_bucket
    cells = {block_arm_bucket(res.bucket, i, k) for i in range(k)}
    assert cells <= {key[0] for key in session.adaptive._cells}


def test_observe_partitioned_block_arms_and_drift_eviction():
    """Each (block, format) pair is its own bandit arm; sustained measured
    drift on a block evicts the composite plan so the next request
    re-plans, and the block's cell promotes the measured-best format."""
    dense = hetero_matrix()
    session = AutoSpmvSession(
        stub_tuner(),
        telemetry=TelemetryRecorder(),
        adaptive=AdaptiveFormatSelector(),
    )
    res = session.partitioned_optimize(dense, "latency")
    k = res.n_blocks
    assert k > 1
    # establish incumbent cells with on-plan measurements
    session.observe_partitioned(res, [0.01] * k)

    # a challenger format measures 10x faster on every block ...
    challenger = "sell" if "sell" not in res.plan.formats else "bcsr_none"
    assert challenger == "sell"  # stub plans never pick sell here
    probe = dataclasses.replace(
        res, served_formats=(challenger,) * k, exploratory=(True,) * k
    )
    for _ in range(3):
        session.observe_partitioned(probe, [0.001] * k)
    # ... while the incumbent keeps drifting: sustained -> eviction
    for _ in range(6):
        session.observe_partitioned(res, [0.01] * k)
    assert session.stats.invalidations >= 1
    assert session.cache.peek(res.bucket, "latency", res.mode) is None
    # the promoted block cell now serves the measured-best format
    promoted = session.adaptive.incumbent(
        block_arm_bucket(res.bucket, 0, k), "latency"
    )
    assert promoted == challenger


def test_serve_partitioned_without_adaptive_is_plain_optimize():
    dense = hetero_matrix()
    session = AutoSpmvSession(stub_tuner())
    res = session.serve_partitioned(dense, "latency")
    assert res.served_formats == ()
    assert res.formats == res.plan.formats


def test_partitioned_spmv_rejects_empty():
    with pytest.raises(ValueError):
        PartitionedSpmv([], 0)
