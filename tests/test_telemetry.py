"""Tests for the telemetry + adaptive reoptimization subsystem: recorder
aggregation and JSONL restart survival, the UCB bandit's corrupted-prior
recovery and drift-triggered cache invalidation, the feedback export/refit
path, crash-safe persistence, and the SpmvServer integration."""

import json
import math

import numpy as np
import pytest

from repro.core import (
    AutoSpMV,
    AutoSpmvPredictor,
    AutoSpmvSession,
    PredictorConfig,
    TuningCache,
    TuningDataset,
    extract_features,
)
from repro.core.cache import CacheEntry
from repro.core.predictor import OBJECTIVES
from repro.kernels.common import DEFAULT_SCHEDULE, InfeasibleConfig
from repro.sparse.generate import random_matrix
from repro.telemetry import (
    AdaptiveConfig,
    AdaptiveFormatSelector,
    FeedbackConfig,
    FeedbackLoop,
    TelemetryRecorder,
    telemetry_records,
)
from repro.utils.io import atomic_write_text

FORMATS = ("csr", "ell", "bell", "sell")
TRUE_LAT = {"csr": 0.001, "ell": 0.010, "bell": 0.020, "sell": 0.030}


class _FakePredictor:
    """Corrupted prior: claims 'ell' wins although csr measures 10x faster."""

    def predict_format(self, feats, objective):
        return "ell"

    def predict_schedule(self, feats, objective):
        return DEFAULT_SCHEDULE

    def estimate_objective(self, feats, config, objective):
        return 0.005 if config.fmt == "ell" else 0.02


class _FakeOverhead:
    def total_overhead(self, feats, fmt):
        return 1.0

    def predict_c(self, feats, fmt):
        return 0.5


def _fake_tuner():
    return AutoSpMV(_FakePredictor(), _FakeOverhead())


def _mat(seed=0, n=128):
    return random_matrix(n, 6.0, "fem", seed=seed)


# ------------------------------------------------------------------ recorder
def test_recorder_aggregates_per_arm():
    rec = TelemetryRecorder()
    for t in (1.0, 2.0, 3.0):
        rec.observe(bucket="b1", objective="latency", fmt="csr", measured_s=t)
    rec.observe(bucket="b1", objective="latency", fmt="ell", measured_s=9.0)
    arm = rec.arm("b1", "latency", "csr")
    assert arm.stats.count == 3
    assert arm.stats.mean == pytest.approx(2.0)
    assert arm.stats.percentile(50) == pytest.approx(2.0)
    assert rec.arms_for("b1", "latency").keys() == {"csr", "ell"}
    assert rec.total_observations() == 4
    s = rec.summary()
    assert s["arms"] == 2 and s["buckets"] == 1 and s["observations"] == 4


def test_recorder_tracks_features_and_exploration():
    rec = TelemetryRecorder()
    feats = extract_features(_mat()).dict()
    rec.observe(
        bucket="b1", objective="latency", fmt="csr", measured_s=1.0,
        features=feats, schedule=DEFAULT_SCHEDULE.as_dict(), exploratory=True,
    )
    assert rec.bucket_features("b1") == feats
    assert rec.arm("b1", "latency", "csr").exploratory_pulls == 1
    assert rec.arm("b1", "latency", "csr").schedule == DEFAULT_SCHEDULE.as_dict()


def test_recorder_log_survives_restart(tmp_path):
    log = tmp_path / "telemetry.jsonl"
    feats = extract_features(_mat()).dict()
    rec = TelemetryRecorder(log_path=log, flush_every=2)
    for i, fmt in enumerate(["csr", "csr", "ell"]):
        rec.observe(
            bucket="b1", objective="latency", fmt=fmt,
            measured_s=TRUE_LAT[fmt] * (1 + 0.1 * i), features=feats,
        )
    rec.flush()
    reborn = TelemetryRecorder(log_path=log)
    assert reborn.total_observations() == 3
    assert reborn.records_dropped == 0
    assert reborn.arm("b1", "latency", "csr").stats.count == 2
    assert reborn.arm("b1", "latency", "csr").stats.mean == pytest.approx(
        rec.arm("b1", "latency", "csr").stats.mean
    )
    assert reborn.bucket_features("b1") == feats
    assert reborn.seq > rec.seq - 1  # new records continue, never reuse seq


def test_recorder_auto_flush_threshold(tmp_path):
    log = tmp_path / "t.jsonl"
    rec = TelemetryRecorder(log_path=log, flush_every=2)
    rec.observe(bucket="b", objective="latency", fmt="csr", measured_s=1.0)
    assert not log.exists() or log.read_text() == ""  # still pending
    rec.observe(bucket="b", objective="latency", fmt="csr", measured_s=1.0)
    assert len(log.read_text().splitlines()) == 2  # hit flush_every


def test_recorder_skips_torn_trailing_line(tmp_path):
    log = tmp_path / "t.jsonl"
    rec = TelemetryRecorder(log_path=log, flush_every=1)
    rec.observe(bucket="b", objective="latency", fmt="csr", measured_s=1.0)
    rec.observe(bucket="b", objective="latency", fmt="csr", measured_s=2.0)
    with open(log, "a") as f:
        f.write('{"seq": 7, "bucket": "b", "measu')  # crash mid-append
    reborn = TelemetryRecorder(log_path=log)
    assert reborn.total_observations() == 2
    assert reborn.records_dropped == 1
    # appending after recovery must not glue onto the torn line
    reborn.observe(bucket="b", objective="latency", fmt="ell", measured_s=3.0)
    reborn.flush()
    again = TelemetryRecorder(log_path=log)
    assert again.total_observations() == 3
    assert again.arm("b", "latency", "ell").stats.count == 1


def test_recorder_without_log_path_stays_in_memory():
    rec = TelemetryRecorder()
    rec.observe(bucket="b", objective="latency", fmt="csr", measured_s=1.0)
    assert rec.flush() == 0  # nothing pending, nowhere to write
    assert rec.total_observations() == 1


# ------------------------------------------------------------------- adaptive
def _drive(sel, n, incumbent="ell", noise=None):
    served = []
    for i in range(n):
        fmt, _ = sel.choose("b", "latency", incumbent, FORMATS, prior_value=0.005)
        measured = TRUE_LAT[fmt]
        if noise is not None:
            measured *= 1 + noise * math.sin(i)
        sel.update("b", "latency", fmt, measured, predicted_s=0.005)
        challenger = sel.review("b", "latency")
        if challenger is not None:
            sel.promote("b", "latency", challenger)
        served.append(fmt)
    return served


def test_bandit_serves_incumbent_when_budget_spent():
    sel = AdaptiveFormatSelector(AdaptiveConfig(exploration_fraction=0.2))
    served = _drive(sel, 40)
    cell = sel._cells[("b", "latency")]
    # exploration stays within the configured fraction (+1 slack for the bootstrap)
    assert cell.exploration_pulls <= 0.2 * (cell.total_pulls + 1) + 1
    assert served.count("ell") + served.count("csr") > len(served) / 2


def test_bandit_recovers_from_corrupted_prior():
    """The acceptance path: incumbent 'ell' is a misprediction; measured
    wall times must promote 'csr' and keep serving it."""
    sel = AdaptiveFormatSelector(
        AdaptiveConfig(exploration_fraction=0.4, drift_window=3, min_challenger_pulls=1)
    )
    served = _drive(sel, 40, noise=0.02)
    assert sel.incumbent("b", "latency") == "csr"
    cell = sel._cells[("b", "latency")]
    assert cell.invalidations >= 1 and cell.promoted
    # once converged, the incumbent dominates the serving mix
    assert served[-10:].count("csr") >= 6


def test_bandit_adopts_replanned_incumbent():
    sel = AdaptiveFormatSelector()
    sel.choose("b", "latency", "ell", FORMATS, prior_value=0.005)
    # a cache re-plan (e.g. after refit) hands a different incumbent
    sel.choose("b", "latency", "csr", FORMATS, prior_value=0.001)
    assert sel.incumbent("b", "latency") == "csr"


def test_bandit_promotion_clears_when_model_catches_up():
    sel = AdaptiveFormatSelector(
        AdaptiveConfig(exploration_fraction=0.4, drift_window=3, min_challenger_pulls=1)
    )
    _drive(sel, 40)
    assert sel._cells[("b", "latency")].promoted
    # the refit classifier now also says 'csr': promotion pin is released
    sel.choose("b", "latency", "csr", FORMATS, prior_value=0.001)
    assert not sel._cells[("b", "latency")].promoted
    assert sel.incumbent("b", "latency") == "csr"


def test_bandit_prior_never_contaminates_measured_mean():
    """The model's estimate may be on a completely different scale than the
    measured wall times (TPU cost model vs CPU interpret); it seeds the UCB
    value but must stay out of the measured statistics."""
    sel = AdaptiveFormatSelector()
    sel.choose("b", "latency", "ell", FORMATS, prior_value=1e-6)  # model scale
    for _ in range(3):
        sel.update("b", "latency", "ell", 1e-3)  # measured scale, 1000x larger
    arm = sel._cells[("b", "latency")].arms["ell"]
    assert arm.stats.mean == pytest.approx(1e-3)  # measured only
    assert arm.prior_value == pytest.approx(1e-6)
    assert arm.value() == pytest.approx(1e-3)  # real pulls outrank the prior


def test_bandit_model_drift_alone_never_evicts():
    """Every measurement exceeding its estimate (wrong cost-model scale) and
    a noise-level challenger advantage must not thrash the cache: eviction
    needs a challenger better by the full drift_threshold margin."""
    sel = AdaptiveFormatSelector(
        AdaptiveConfig(drift_window=2, min_challenger_pulls=1, drift_threshold=0.25)
    )
    sel.choose("b", "latency", "ell", FORMATS, prior_value=1e-6)
    sel.update("b", "latency", "csr", 0.99e-3)  # challenger: only 1% better
    for _ in range(10):
        sel.update("b", "latency", "ell", 1e-3, predicted_s=1e-6)  # drifted vs model
        assert sel.review("b", "latency") is None
    assert sel.incumbent("b", "latency") == "ell"


def test_bandit_disabled_incumbent_falls_back():
    """If the cached plan's own format is infeasible, the cell must promote
    a servable arm — a budget-closed choose() may never return it."""
    sel = AdaptiveFormatSelector(AdaptiveConfig(exploration_fraction=0.01))
    sel.choose("b", "latency", "ell", FORMATS, prior_value=0.005)
    sel.disable("b", "latency", "ell", fallback="csr")
    assert sel.incumbent("b", "latency") == "csr"
    for _ in range(20):  # budget closes immediately at 1% exploration
        fmt, _ = sel.choose("b", "latency", "ell", FORMATS, prior_value=0.005)
        assert fmt != "ell"
        sel.update("b", "latency", fmt, 0.001)


def test_bandit_warm_start_from_recorder():
    rec = TelemetryRecorder()
    for fmt in ("csr", "csr", "ell"):
        rec.observe(bucket="b", objective="latency", fmt=fmt, measured_s=TRUE_LAT[fmt])
    sel = AdaptiveFormatSelector()
    assert sel.warm_start(rec) == 2  # one seed per distinct arm
    cell = sel._cells[("b", "latency")]
    assert set(cell.arms) == {"csr", "ell"}


# --------------------------------------------------------- session integration
def test_serve_optimize_without_adaptive_serves_cached_plan():
    session = AutoSpmvSession(_fake_tuner())
    dense = _mat()
    p1 = session.serve_optimize(dense)
    assert p1.fmt == "csr" and not p1.exploratory and not p1.cache_hit
    p2 = session.serve_optimize(dense)
    assert p2.cache_hit and p2.kernel is p1.kernel
    x = np.random.default_rng(0).normal(size=dense.shape[1]).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(p1.kernel(x)), dense @ x, rtol=1e-4, atol=1e-4
    )


def test_telemetry_only_session_records_without_changing_decisions(tmp_path):
    rec = TelemetryRecorder(log_path=tmp_path / "t.jsonl", flush_every=1)
    session = AutoSpmvSession(_fake_tuner(), telemetry=rec)
    dense = _mat()
    for _ in range(3):
        plan = session.serve_optimize(dense)
        assert plan.fmt == "csr"  # no bandit: the cached plan is served as-is
        session.observe(plan, 0.002)
    assert session.stats.observations == 3
    assert session.stats.explorations == 0
    assert rec.total_observations() == 3
    assert rec.bucket_features(plan.bucket) == plan.features.dict()


def test_session_drift_invalidates_cache_and_replans():
    sel = AdaptiveFormatSelector(
        AdaptiveConfig(exploration_fraction=0.4, drift_window=3, min_challenger_pulls=1)
    )
    session = AutoSpmvSession(_fake_tuner(), telemetry=TelemetryRecorder(), adaptive=sel)
    dense = _mat()
    for _ in range(25):
        plan = session.serve_optimize(dense)
        session.observe(plan, TRUE_LAT[plan.fmt])
    assert session.stats.invalidations >= 1
    assert sel.incumbent(plan.bucket, "latency") == "csr"
    # post-eviction requests re-planned and serve the measured-best format
    final = session.serve_optimize(dense)
    assert final.fmt == "csr"


def test_serve_optimize_falls_back_when_exploration_infeasible(monkeypatch):
    """A bandit probe into an infeasible format must not fail the request."""
    sel = AdaptiveFormatSelector(AdaptiveConfig(exploration_fraction=1.0))
    session = AutoSpmvSession(_fake_tuner(), adaptive=sel)
    dense = _mat()
    orig = session._compile

    def explode_non_csr(d, fp, fmt, schedule):
        if fmt != "csr":
            raise InfeasibleConfig(f"{fmt} storage would be huge")
        return orig(d, fp, fmt, schedule)

    monkeypatch.setattr(session, "_compile", explode_non_csr)
    attempts = []
    real_explode = explode_non_csr

    def counting(d, fp, fmt, schedule):
        attempts.append(fmt)
        return real_explode(d, fp, fmt, schedule)

    monkeypatch.setattr(session, "_compile", counting)
    for _ in range(12):
        plan = session.serve_optimize(dense)
        assert plan.fmt == "csr" and plan.kernel is not None
        session.observe(plan, 0.001)
    # each infeasible format is probed once, then disabled — never re-tried
    non_csr = [f for f in attempts if f != "csr"]
    assert len(non_csr) == len(set(non_csr))
    bucket = plan.bucket
    cell = sel._cells[(bucket, "latency")]
    assert all(cell.arms[f].disabled for f in set(non_csr))


def test_session_invalidate_filters():
    session = AutoSpmvSession(_fake_tuner())
    for obj in ("latency", "energy"):
        session.cache.put(
            CacheEntry(bucket="b1", objective=obj, mode="compile", fmt="csr",
                       schedule=DEFAULT_SCHEDULE.as_dict())
        )
    assert session.invalidate("b1", "latency") == 1
    assert session.stats.invalidations == 1
    assert session.cache.peek("b1", "energy", "compile") is not None
    assert session.invalidate("missing") == 0
    assert session.stats.invalidations == 1  # no-op evictions are not counted


# ------------------------------------------------------------------- feedback
def _seed_measurements(rec, feats_dict, objective="latency"):
    for fmt in ("csr", "ell"):
        for rep in range(3):
            rec.observe(
                bucket="b1", objective=objective, fmt=fmt,
                measured_s=TRUE_LAT[fmt] * (1 + 0.01 * rep),
                features=feats_dict, schedule=DEFAULT_SCHEDULE.as_dict(),
            )


def test_telemetry_records_export_dataset_rows():
    rec = TelemetryRecorder()
    feats = extract_features(_mat()).dict()
    _seed_measurements(rec, feats)
    rows = telemetry_records(rec)
    assert len(rows) == 2
    by_fmt = {r.config.fmt: r for r in rows}
    assert by_fmt["csr"].latency == pytest.approx(TRUE_LAT["csr"] * 1.01, rel=0.02)
    assert by_fmt["csr"].source == "telemetry_latency"
    assert math.isnan(by_fmt["csr"].energy)  # unmeasured objectives stay NaN
    assert by_fmt["csr"].matrix == "telemetry/b1"


def test_feedback_export_appends_and_supersedes(tmp_path):
    rec = TelemetryRecorder()
    feats = extract_features(_mat()).dict()
    _seed_measurements(rec, feats)
    loop = FeedbackLoop(rec, dataset_path=tmp_path / "ds.json")
    ds = loop.export_dataset()
    n_first = len(ds)
    # more traffic, re-export into the same dataset: superseded, not duplicated
    _seed_measurements(rec, feats)
    ds = loop.export_dataset(ds)
    assert len(ds) == n_first
    reloaded = TuningDataset.load(tmp_path / "ds.json")
    assert len(reloaded) == n_first
    assert all(r.source.startswith("telemetry") for r in reloaded.records)


def test_feedback_refit_flips_corrupted_classifier(tmp_path):
    """Acceptance: telemetry log + refit state survive a process restart —
    a recorder replayed from disk must drive the same classifier repair."""
    log = tmp_path / "telemetry.jsonl"
    rec = TelemetryRecorder(log_path=log, flush_every=4)
    feats = extract_features(_mat())
    _seed_measurements(rec, feats.dict())
    rec.flush()

    # "restart": rebuild the recorder from the log, then refit from it
    reborn = TelemetryRecorder(log_path=log)
    loop = FeedbackLoop(reborn)
    predictor = AutoSpmvPredictor(PredictorConfig())
    predictor.format_clf_ = {obj: None for obj in OBJECTIVES}
    used = loop.refit_format_classifier(predictor, objectives=("latency",))
    assert used == {"latency": 1}
    assert loop.refits == 1
    assert predictor.predict_format(feats, "latency") == "csr"  # measured best


def test_feedback_refit_respects_min_coverage():
    rec = TelemetryRecorder()
    feats = extract_features(_mat()).dict()
    rec.observe(bucket="b1", objective="latency", fmt="csr", measured_s=1.0,
                features=feats)  # one format, one pull: not informative
    loop = FeedbackLoop(rec)
    predictor = AutoSpmvPredictor(PredictorConfig())
    predictor.format_clf_ = {}
    assert loop.refit_format_classifier(predictor, objectives=("latency",)) == {}


def test_feedback_maybe_refit_gates_on_new_observations():
    rec = TelemetryRecorder()
    feats = extract_features(_mat()).dict()
    loop = FeedbackLoop(rec, config=FeedbackConfig(refit_every=7))
    predictor = AutoSpmvPredictor(PredictorConfig())
    predictor.format_clf_ = {}
    assert loop.maybe_refit(predictor) == {}  # nothing recorded yet
    _seed_measurements(rec, feats)  # 6 observations < 7
    assert loop.maybe_refit(predictor) == {}
    rec.observe(bucket="b1", objective="latency", fmt="csr", measured_s=0.001,
                features=feats)
    assert loop.maybe_refit(predictor) == {"latency": 1}


def test_feedback_refit_merges_base_dataset_labels():
    rec = TelemetryRecorder()
    m1, m2 = _mat(seed=1), random_matrix(512, 24.0, "powerlaw", seed=2)
    _seed_measurements(rec, extract_features(m1).dict())
    # base dataset covers a second matrix the fleet never measured
    from repro.core import collect_dataset

    base = collect_dataset(scale=0.0012, names=(), n_extra=2)
    loop = FeedbackLoop(rec, base_dataset=base)
    predictor = AutoSpmvPredictor(PredictorConfig())
    predictor.format_clf_ = {}
    used = loop.refit_format_classifier(predictor, objectives=("latency",))
    assert used["latency"] == 1
    # the refit classifier answers for unmeasured features too (base coverage)
    assert predictor.predict_format(extract_features(m2), "latency") in FORMATS


# ----------------------------------------------------------- crash-safe saves
def test_atomic_write_keeps_old_content_on_failure(tmp_path, monkeypatch):
    p = tmp_path / "cache.json"
    atomic_write_text(p, "old")
    import repro.utils.io as io_mod

    def boom(src, dst):
        raise OSError("disk full")

    monkeypatch.setattr(io_mod.os, "replace", boom)
    with pytest.raises(OSError):
        atomic_write_text(p, "new")
    assert p.read_text() == "old"
    assert list(tmp_path.glob("*.tmp")) == []  # temp file cleaned up


def test_cache_save_is_atomic(tmp_path, monkeypatch):
    cache = TuningCache()
    cache.put(CacheEntry(bucket="b1", objective="latency", mode="compile",
                         fmt="csr", schedule=DEFAULT_SCHEDULE.as_dict()))
    path = tmp_path / "cache.json"
    cache.save(path)
    assert list(tmp_path.glob("*.tmp")) == []
    cache.put(CacheEntry(bucket="b2", objective="latency", mode="compile",
                         fmt="ell", schedule=DEFAULT_SCHEDULE.as_dict()))
    import repro.utils.io as io_mod

    monkeypatch.setattr(
        io_mod.os, "replace", lambda s, d: (_ for _ in ()).throw(OSError("boom"))
    )
    with pytest.raises(OSError):
        cache.save(path)
    monkeypatch.undo()
    loaded = TuningCache.load(path)  # old file intact: warm restart still works
    assert len(loaded) == 1


# ----------------------------------------------------------------- SpmvServer
def test_spmv_server_adaptive_end_to_end(tmp_path):
    from repro.train.serve import SpmvRequest, SpmvServer

    rec = TelemetryRecorder(log_path=tmp_path / "t.jsonl", flush_every=4)
    sel = AdaptiveFormatSelector(AdaptiveConfig(exploration_fraction=0.3))
    session = AutoSpmvSession(_fake_tuner(), telemetry=rec, adaptive=sel)
    loop = FeedbackLoop(rec, config=FeedbackConfig(refit_every=4))
    server = SpmvServer(session, feedback=loop)
    assert server.adaptive  # auto-detected from the session

    rng = np.random.default_rng(0)
    mats = [_mat(seed=s, n=96 + 32 * s) for s in range(2)]
    reqs = [
        SpmvRequest(rid=i, dense=mats[i % 2],
                    x=rng.normal(size=mats[i % 2].shape[1]).astype(np.float32))
        for i in range(6)
    ]
    done = server.run(reqs)
    for r in done:
        assert r.fmt in FORMATS and r.schedule is not None
        assert r.latency_s > 0
        ref = r.dense @ r.x
        err = np.abs(r.y - ref).max() / (np.abs(ref).max() + 1e-9)
        assert err < 1e-3  # explored formats still compute the right answer
    assert session.stats.observations == len(reqs)
    s = server.summary()
    assert s["requests"] == len(reqs)
    assert s["telemetry"]["observations"] == len(reqs)
    assert "adaptive" in s and "refits" in s
    rec.flush()
    lines = (tmp_path / "t.jsonl").read_text().splitlines()
    assert len(lines) == len(reqs)
    assert all(json.loads(l)["measured_s"] > 0 for l in lines)


def test_spmv_server_plain_mode_unchanged():
    from repro.train.serve import SpmvRequest, SpmvServer

    session = AutoSpmvSession(_fake_tuner())
    server = SpmvServer(session)
    assert not server.adaptive  # no telemetry attached: PR-1 batch path
    rng = np.random.default_rng(1)
    m = _mat()
    reqs = [SpmvRequest(rid=i, dense=m,
                        x=rng.normal(size=m.shape[1]).astype(np.float32))
            for i in range(3)]
    done = server.run(reqs)
    assert all(r.y is not None and r.fmt is None for r in done)
    assert session.stats.observations == 0


def test_spmv_server_summary_latency_and_energy():
    """summary() surfaces p50/p90/p99 request latency per objective and the
    per-format energy/power accounting (PR-7 observability satellite)."""
    from repro.obs import set_obs_enabled
    from repro.obs.metrics import reset_metrics
    from repro.obs.trace import get_tracer
    from repro.train.serve import SpmvRequest, SpmvServer

    set_obs_enabled(True)
    reset_metrics()
    get_tracer().clear()
    try:
        sel = AdaptiveFormatSelector(AdaptiveConfig(exploration_fraction=0.0))
        session = AutoSpmvSession(
            _fake_tuner(), telemetry=TelemetryRecorder(), adaptive=sel
        )
        server = SpmvServer(session)
        rng = np.random.default_rng(2)
        m = _mat()
        reqs = [
            SpmvRequest(rid=i, dense=m,
                        x=rng.normal(size=m.shape[1]).astype(np.float32))
            for i in range(5)
        ]
        server.run(reqs)

        s = server.summary()
        lat = s["latency"]["latency"]  # keyed by objective
        assert lat["count"] == len(reqs)
        assert 0 < lat["p50"] <= lat["p90"] <= lat["p99"]
        assert lat["sum"] >= lat["count"] * lat["p50"] * 0.1  # sane magnitudes

        assert s["energy"], "per-format energy cells missing"
        for fmt, cell in s["energy"].items():
            assert fmt in FORMATS
            assert cell["requests"] > 0
            assert cell["energy_j"] >= 0
            assert cell["avg_power_w"] >= 0
            assert cell["efficiency_mflops_per_w"] >= 0
        # modeled objectives flowed through: the served format carries energy
        assert sum(c["requests"] for c in s["energy"].values()) == len(reqs)
    finally:
        reset_metrics()
        get_tracer().clear()
