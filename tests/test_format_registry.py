"""Format-registry contract suite + the fifth-format (BCSR) e2e proof.

The contract tests are parametrized over *every* registered format (the four
seeds plus the BCSR plugin), so any future ``register_format()`` plugin that
is added to ``CONTRACT_FORMATS`` is validated for free:

* dense round-trip through ``from_dense``/``to_dense`` is exact;
* ``prepare`` + ``spmv`` matches the dense oracle (``kernels/ref.py``) over
  schedules, including bf16 accumulation and empty rows;
* the pure-jnp ``reference`` oracle matches the dense product;
* running ``spmv`` on storage prepared with a *different* schedule either
  computes the exact result or raises ``InfeasibleConfig`` — never silently
  corrupts;
* the ``footprint`` model returns finite, non-negative statistics with
  ``useful_flops == 2 * nnz``, and the cost model evaluates it.

``test_bcsr_flows_end_to_end`` is the API-redesign acceptance check: a
format registered *only* via ``register_format()`` (no edits to
ops/tuning_space/objectives/session/adaptive) appears in ``full_space()``,
the tuning dataset, the bandit arm set, and serves correctly through
``SpmvServer``.
"""

import math
import re
from dataclasses import dataclass
from pathlib import Path

import numpy as np
import pytest

from repro.core import (
    MatrixStats,
    TpuCostModel,
    TuningConfig,
    collect_dataset,
    footprint,
    full_space,
)
from repro.core.autotuner import AutoSpMV
from repro.core.predictor import AutoSpmvPredictor, PredictorConfig
from repro.core.session import AutoSpmvSession
from repro.kernels.common import DEFAULT_SCHEDULE, KernelSchedule
from repro.sparse import registry as reg
from repro.sparse.generate import MATRIX_NAMES, random_matrix

SEED_FORMATS = ("csr", "ell", "bell", "sell")
CONTRACT_FORMATS = SEED_FORMATS + ("bcsr",)

CONTRACT_SCHEDULES = [
    DEFAULT_SCHEDULE,
    KernelSchedule(rows_per_block=32, nnz_tile=256, unroll=2),
    KernelSchedule(rows_per_block=16, nnz_tile=128, accum_dtype="bfloat16"),
]


@pytest.fixture()
def with_bcsr():
    """Activate the BCSR plugin for one test, then restore the seed set.

    ``unregister_format`` itself evicts the plugin's memoized kernels, so
    no manual memo hygiene is needed here."""
    from repro.sparse import bcsr

    bcsr.register()
    yield
    reg.unregister_format("bcsr")


def _dense(pattern="fem", n=150, avg=7.0, seed=3):
    return random_matrix(n, avg, pattern, seed=seed).astype(np.float32)


# ------------------------------------------------------------------ contracts
@pytest.mark.parametrize("fmt", CONTRACT_FORMATS)
def test_contract_roundtrip_exact(fmt, with_bcsr):
    spec = reg.get_format(fmt)
    for pattern in ("fem", "powerlaw"):
        dense = _dense(pattern)
        np.testing.assert_array_equal(spec.to_dense(spec.from_dense(dense)), dense)


@pytest.mark.parametrize("fmt", CONTRACT_FORMATS)
def test_contract_prepare_spmv_matches_dense(fmt, with_bcsr):
    spec = reg.get_format(fmt)
    dense = _dense("powerlaw", n=200, avg=8.0, seed=9)
    x = np.random.default_rng(0).normal(size=dense.shape[1]).astype(np.float32)
    ref = dense @ x
    scale = np.abs(ref).max() + 1e-9
    for sched in CONTRACT_SCHEDULES:
        mat = spec.prepare(dense, sched)
        y = np.asarray(spec.spmv(mat, x, sched))
        assert y.shape == (dense.shape[0],)
        tol = 3e-2 if sched.accum_dtype == "bfloat16" else 1e-4
        np.testing.assert_allclose(y / scale, ref / scale, atol=tol, rtol=tol)


@pytest.mark.parametrize("fmt", CONTRACT_FORMATS)
def test_contract_empty_rows_exact_zero(fmt, with_bcsr):
    spec = reg.get_format(fmt)
    dense = np.zeros((64, 64), dtype=np.float32)
    dense[10, 3] = 2.0
    dense[50, 60] = -1.5
    x = np.ones(64, dtype=np.float32)
    mat = spec.prepare(dense, DEFAULT_SCHEDULE)
    y = np.asarray(spec.spmv(mat, x, DEFAULT_SCHEDULE))
    np.testing.assert_allclose(y, dense @ x, atol=1e-6)


@pytest.mark.parametrize("fmt", CONTRACT_FORMATS)
def test_contract_reference_oracle(fmt, with_bcsr):
    spec = reg.get_format(fmt)
    dense = _dense("fem", n=120, avg=6.0, seed=5)
    x = np.random.default_rng(1).normal(size=dense.shape[1]).astype(np.float32)
    y = np.asarray(spec.reference(spec.from_dense(dense), x))
    np.testing.assert_allclose(y, dense @ x, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("fmt", CONTRACT_FORMATS)
def test_contract_misaligned_schedule_exact_or_infeasible(fmt, with_bcsr):
    """A (format, schedule) mismatch must never silently corrupt the result:
    either the kernel re-aligns/computes exactly, or it raises
    ``InfeasibleConfig``."""
    spec = reg.get_format(fmt)
    dense = _dense("fem", n=100, avg=6.0, seed=5)
    x = np.ones(dense.shape[1], dtype=np.float32)
    mat = spec.prepare(dense, KernelSchedule(nnz_tile=128))
    for other in (KernelSchedule(nnz_tile=512), KernelSchedule(rows_per_block=256)):
        try:
            y = np.asarray(spec.spmv(mat, x, other))
        except reg.InfeasibleConfig:
            continue
        np.testing.assert_allclose(y, dense @ x, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("fmt", CONTRACT_FORMATS)
def test_contract_footprint_finite_and_costed(fmt, with_bcsr):
    stats = MatrixStats(_dense("powerlaw", n=256, avg=8.0, seed=0))
    model = TpuCostModel()
    for sched in CONTRACT_SCHEDULES:
        fp = footprint(stats, fmt, sched)
        vals = (
            fp.useful_flops, fp.total_flops, fp.hbm_bytes, fp.gather_elems,
            fp.scatter_elems, fp.grid_steps, fp.mxu_fraction,
            fp.vmem_resident_bytes,
        )
        assert all(math.isfinite(v) and v >= 0.0 for v in vals)
        assert fp.useful_flops == 2.0 * stats.nnz
        assert fp.total_flops >= fp.useful_flops
        assert fp.hbm_bytes > 0 and fp.grid_steps > 0
    # the default schedule must be a feasible point the cost model can rank
    v = model.evaluate(stats, fmt, DEFAULT_SCHEDULE)
    assert v.feasible and v.latency > 0 and v.energy > 0 and v.efficiency > 0


# --------------------------------------------------------------- registry API
def test_registry_seed_state():
    assert reg.format_names() == SEED_FORMATS
    assert reg.default_format() == "csr"
    specs = reg.registered_specs()
    assert tuple(s.name for s in specs) == SEED_FORMATS


def test_registry_rejects_duplicates_and_unknowns():
    csr_spec = reg.get_format("csr")
    with pytest.raises(ValueError, match="already registered"):
        reg.register_format(csr_spec)
    with pytest.raises(ValueError, match="unknown format"):
        reg.get_format("nope")
    with pytest.raises(ValueError, match="not registered"):
        reg.unregister_format("nope")
    with pytest.raises(TypeError, match="no registered format"):
        reg.spec_for(object())


def test_register_dummy_format_appears_everywhere():
    @dataclass(frozen=True)
    class DummyMat:
        dense: np.ndarray
        shape: tuple

    fail = lambda *a, **kw: (_ for _ in ()).throw(NotImplementedError)
    spec = reg.FormatSpec(
        name="dummyfmt",
        container=DummyMat,
        from_dense=lambda d, **kw: DummyMat(np.asarray(d), np.asarray(d).shape),
        to_dense=lambda m: m.dense,
        prepare=fail,
        spmv=fail,
        reference=fail,
        footprint=fail,
        priority=999,
    )
    reg.register_format(spec)
    try:
        assert "dummyfmt" in reg.format_names()
        assert reg.format_names()[-1] == "dummyfmt"  # priority-ordered
        assert reg.default_format() == "csr"  # plugins never displace it
        assert reg.spec_for(DummyMat(np.zeros((1, 1)), (1, 1))).name == "dummyfmt"
        # the tuning space picks it up with zero edits
        assert any(c.fmt == "dummyfmt" for c in full_space())
        # duplicate container under a different name is rejected
        clone = reg.FormatSpec(**{**spec.__dict__, "name": "dummyfmt2"})
        with pytest.raises(ValueError, match="already bound"):
            reg.register_format(clone)
    finally:
        reg.unregister_format("dummyfmt")
    assert "dummyfmt" not in reg.format_names()


def test_unregister_evicts_memoized_kernels(with_bcsr):
    """A memoized PreparedSpmv must not outlive its FormatSpec: serving a
    stale hit after unregistration would crash (or run the old code)."""
    from repro.kernels.ops import compile_spmv, kernel_memoized

    dense = _dense()
    compile_spmv(dense, "bcsr", DEFAULT_SCHEDULE, memo_key="reg-evict")
    compile_spmv(dense, "csr", DEFAULT_SCHEDULE, memo_key="reg-evict")
    assert kernel_memoized("reg-evict", "bcsr", DEFAULT_SCHEDULE)
    reg.unregister_format("bcsr")
    try:
        assert not kernel_memoized("reg-evict", "bcsr", DEFAULT_SCHEDULE)
        # unrelated formats' entries survive
        assert kernel_memoized("reg-evict", "csr", DEFAULT_SCHEDULE)
    finally:
        from repro.sparse import bcsr

        bcsr.register()  # the fixture teardown unregisters again


def test_default_config_tracks_registry_default(with_bcsr):
    """DEFAULT_CONFIG is resolved per access: a plugin registering below the
    seeds' priority becomes the baseline everywhere at once."""
    import repro.core.tuning_space as ts
    from repro.core import compile_time_space

    assert ts.DEFAULT_CONFIG.fmt == "csr"
    bcsr_spec = reg.get_format("bcsr")
    reg.register_format(
        reg.FormatSpec(**{**bcsr_spec.__dict__, "priority": -1}), overwrite=True
    )
    try:
        assert reg.default_format() == "bcsr"
        assert ts.DEFAULT_CONFIG.fmt == "bcsr"
        import repro.core

        assert repro.core.DEFAULT_CONFIG.fmt == "bcsr"
        assert all(c.fmt == "bcsr" for c in compile_time_space())
    finally:
        reg.register_format(bcsr_spec, overwrite=True)
    assert ts.DEFAULT_CONFIG.fmt == "csr"


# -------------------------------------------------------- fifth format: e2e
def test_bcsr_row_compression_beats_bell_on_skew(with_bcsr):
    """The CMRS argument: on skewed block occupancy BCSR stores fewer
    blocks than BELL's per-block-row ELL padding."""
    dense = _dense("powerlaw", n=1024, avg=3.0, seed=2)
    stats = MatrixStats(dense)
    sched = KernelSchedule(rows_per_block=8)  # fine-grained 8x128 blocks
    fp_bell = footprint(stats, "bell", sched)
    fp_bcsr = footprint(stats, "bcsr", sched)
    assert fp_bcsr.total_flops < fp_bell.total_flops
    bell = reg.get_format("bell").from_dense(dense, br=8)
    bcsr = reg.get_format("bcsr").from_dense(dense, br=8)
    assert bcsr.data.size < bell.data.size


def test_bcsr_flows_end_to_end(with_bcsr):
    from repro.telemetry import AdaptiveConfig, AdaptiveFormatSelector, TelemetryRecorder
    from repro.train.serve import SpmvRequest, SpmvServer

    assert reg.format_names() == CONTRACT_FORMATS
    assert reg.default_format() == "csr"

    # 1. tuning space: bcsr configs appear with zero edits
    assert {c.fmt for c in full_space()} == set(CONTRACT_FORMATS)

    # 2. dataset + classifier labels over the extended space
    scheds = [DEFAULT_SCHEDULE, KernelSchedule(rows_per_block=32, nnz_tile=256, unroll=2)]
    space = [TuningConfig(f, s) for f in reg.format_names() for s in scheds]
    ds = collect_dataset(scale=0.0012, names=MATRIX_NAMES[:3], n_extra=0, space=space)
    bcsr_recs = [r for r in ds.records if r.config.fmt == "bcsr"]
    assert bcsr_recs and any(r.feasible for r in bcsr_recs)
    pred = AutoSpmvPredictor(PredictorConfig(max_regressor_samples=500)).fit(ds)
    assert pred.format_names_ == CONTRACT_FORMATS
    for obj in ("latency", "energy"):
        est = pred.estimate_objective(
            ds.records[0].features, TuningConfig("bcsr", DEFAULT_SCHEDULE), obj
        )
        assert math.isfinite(est) and est > 0

    # 3. bandit arm set + end-to-end serving through SpmvServer
    sel = AdaptiveFormatSelector(AdaptiveConfig(exploration_fraction=1.0))
    session = AutoSpmvSession(
        AutoSpMV(pred), telemetry=TelemetryRecorder(), adaptive=sel
    )
    server = SpmvServer(session)
    dense = _dense("block", n=180, avg=7.0, seed=4)
    rng = np.random.default_rng(0)
    reqs = [
        SpmvRequest(
            rid=i, dense=dense, x=rng.normal(size=dense.shape[1]).astype(np.float32)
        )
        for i in range(8)
    ]
    done = server.run(reqs)
    for r in done:
        ref = r.dense @ r.x
        scale = np.abs(ref).max() + 1e-9
        np.testing.assert_allclose(r.y / scale, ref / scale, rtol=1e-4, atol=1e-4)
    served = [r.fmt for r in done]
    # with the exploration budget wide open every arm gets pulled: the
    # plugin format was actually served (not just registered)
    assert "bcsr" in served
    arms = {fmt for (_, _, fmt) in session.telemetry.arms()}
    assert "bcsr" in arms


# -------------------------------------------------------------------- hygiene
def test_no_format_literal_dispatch_outside_registry():
    """The CI guard, enforced in-tree too: no new ``fmt == "..."`` dispatch
    chains may appear in src/ outside sparse/registry.py."""
    src = Path(__file__).resolve().parent.parent / "src"
    pat = re.compile(r"""fmt\s*==\s*["']""")
    offenders = []
    for p in sorted(src.rglob("*.py")):
        if p.parts[-2:] == ("sparse", "registry.py"):
            continue
        for i, line in enumerate(p.read_text().splitlines(), 1):
            if pat.search(line):
                offenders.append(f"{p.relative_to(src)}:{i}: {line.strip()}")
    assert not offenders, "format-literal dispatch outside the registry:\n" + "\n".join(
        offenders
    )
