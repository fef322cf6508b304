"""Tests for the iterative-solver subsystem (repro.solvers): reference
correctness of PageRank/CG/power, dangling-node stochasticity, the adaptive
SpMV<->SpMSpV policy's density routing and bandit-learned crossover, and
the one-plan amortization contract across a 50-iteration solve."""

import numpy as np
import pytest

from repro.core import AutoSpMV, AutoSpmvSession
from repro.kernels.common import DEFAULT_SCHEDULE, KernelSchedule
from repro.obs.trace import get_tracer
from repro.solvers import AdaptiveSpmvPolicy, IterativeSolver, cg, pagerank, power_iteration
from repro.solvers.adaptive import SPMSPV, SPMV
from repro.solvers.pagerank import pagerank_reference
from repro.sparse.generate import generate_by_name, normalize_columns, random_matrix
from repro.telemetry import AdaptiveFormatSelector, phase_arm_bucket

WEB_SCALE = 0.0002  # webgraph at n=175: interpret-mode-friendly


class _FakePredictor:
    def __init__(self, schedule=DEFAULT_SCHEDULE):
        self.schedule = schedule

    def predict_format(self, feats, objective):
        return "ell"

    def predict_schedule(self, feats, objective):
        return self.schedule

    def estimate_objective(self, feats, config, objective):
        return 0.5 if config.fmt == "ell" else 1.0


class _FakeOverhead:
    def total_overhead(self, feats, fmt):
        return 1e6

    def predict_c(self, feats, fmt):
        return 1.0


def _session(schedule=DEFAULT_SCHEDULE):
    return AutoSpmvSession(AutoSpMV(_FakePredictor(schedule), _FakeOverhead()))


@pytest.fixture
def session():
    return _session()


@pytest.fixture(scope="module")
def web():
    return generate_by_name("webgraph", scale=WEB_SCALE)


# ------------------------------------------------------------------ pagerank
def test_pagerank_matches_dense_reference(session, web):
    res = pagerank(session, web, tol=1e-9, max_iters=300)
    ref = pagerank_reference(web, tol=1e-12)
    assert res.converged
    assert np.abs(res.value - ref).max() < 1e-5
    # reference ranks and served ranks order the top nodes identically
    assert list(np.argsort(res.value)[-5:]) == list(np.argsort(ref)[-5:])


def test_pagerank_dangling_stochasticity(session, web):
    dangling = int((web.sum(axis=0) == 0).sum())
    assert dangling > 0, "webgraph generator must produce dangling nodes"
    res = pagerank(session, web, tol=1e-9, max_iters=300)
    assert res.extras["dangling_nodes"] == dangling
    # dangling-mass redistribution keeps the ranks a probability vector
    assert abs(res.extras["rank_sum"] - 1.0) < 1e-5
    assert np.all(res.value >= 0)


def test_normalize_columns_is_stochastic_except_dangling(web):
    P = normalize_columns(web)
    sums = P.sum(axis=0)
    nonzero = web.sum(axis=0) > 0
    np.testing.assert_allclose(sums[nonzero], 1.0, atol=1e-5)
    assert np.all(sums[~nonzero] == 0)


# ------------------------------------------------------------------------ cg
def _spd(n=128, seed=3):
    F = random_matrix(n, 6.0, "fem", seed=seed).astype(np.float32)
    S = (F + F.T) / 2
    margin = float(np.abs(S).sum(axis=1).max()) + 1.0
    return (S + margin * np.eye(n, dtype=np.float32)).astype(np.float32)


def test_cg_converges_with_decreasing_residuals(session):
    S = _spd()
    b = np.random.default_rng(0).standard_normal(128).astype(np.float32)
    res = cg(session, S, b, tol=1e-10, max_iters=200)
    assert res.converged
    x_ref = np.linalg.solve(S.astype(np.float64), b.astype(np.float64))
    assert np.abs(res.value - x_ref).max() < 1e-5
    # residual history trends down by orders of magnitude
    assert res.residuals[-1] < res.residuals[0] * 1e-6
    drops = sum(b2 < a2 for a2, b2 in zip(res.residuals, res.residuals[1:]))
    assert drops >= len(res.residuals) - 2  # near-monotonic decrease


# ----------------------------------------------------------- adaptive policy
def test_policy_phase_bins_and_threshold_prior():
    pol = AdaptiveSpmvPolicy()
    assert pol.n_phases == 6
    assert pol.phase_of(0.0) == 0
    assert pol.phase_of(0.03) == 1
    assert pol.phase_of(0.9) == 5
    assert pol.prior_kind(0.01) == SPMSPV
    assert pol.prior_kind(0.5) == SPMV
    assert phase_arm_bucket("b1", 2, 6) == "b1#ph2of6"


def test_adaptive_policy_flips_spmspv_to_spmv_as_frontier_densifies(session, web):
    pol = AdaptiveSpmvPolicy()
    res = power_iteration(session, web, tol=0.0, max_iters=12, policy=pol)
    kinds = res.matvec_kinds
    assert kinds[0] == SPMSPV, "seed frontier must route through SpMSpV"
    assert SPMV in kinds, "densified frontier must flip to SpMV"
    flip = kinds.index(SPMV)
    assert all(k == SPMSPV for k in kinds[:flip])
    assert all(k == SPMV for k in kinds[flip:]), "flip must be one-way"
    assert res.spmspv_calls >= 1 and res.spmv_calls >= 1
    # sparse-frontier iterations touched strictly less stored work
    assert res.modeled_work < res.spmv_work_equiv


def test_policy_bandit_learns_crossover():
    """Measured times overturn the threshold prior inside one density phase."""
    pol = AdaptiveSpmvPolicy(selector=AdaptiveFormatSelector())
    density = 0.05  # below threshold: prior says SpMSpV
    assert pol.prior_kind(density) == SPMSPV
    # feed measurements where SpMSpV is 10x slower than SpMV at this phase
    for _ in range(40):
        decision = pol.choose(density)
        pol.update(decision, 1.0 if decision.kind == SPMSPV else 0.1)
    finals = [pol.choose(density).kind for _ in range(8)]
    assert finals.count(SPMV) > finals.count(SPMSPV), (
        f"bandit failed to learn the crossover: {finals}"
    )


# ----------------------------------------------------- amortization contract
SOLVES = {
    "power": lambda session, m, iters: power_iteration(session, m, tol=0.0, max_iters=iters),
    "pagerank": lambda session, m, iters: pagerank(session, m, tol=0.0, max_iters=iters),
}


@pytest.mark.parametrize("solver", sorted(SOLVES))
def test_fifty_iteration_solve_plans_exactly_once(solver, session, web):
    tracer = get_tracer()
    tracer.clear()
    res = SOLVES[solver](session, web, 50)
    assert res.iterations == 50
    stats = session.stats
    assert stats.plans_computed == 1, (
        f"a 50-iteration solve must serve ONE plan, computed {stats.plans_computed}"
    )
    assert stats.observations == 50  # every iteration fed observe()
    spans = tracer.spans()
    iterate = [s for s in spans if s["name"] == "solver.iterate"]
    assert len(iterate) == 50
    assert {s["attrs"]["iteration"] for s in iterate} == set(range(1, 51))
    assert all(s["attrs"]["solver"] == solver for s in iterate)
    # one kernel.execute inside every iteration, and none outside one
    ids = {s["id"] for s in iterate}
    execute = [s for s in spans if s["name"] == "kernel.execute"]
    assert sorted(s["parent"] for s in execute) == sorted(ids)
    assert len({s["root"] for s in spans}) == 1  # one solve, one request id
    # a second solve over the same matrix reuses the cached plan entirely
    res2 = SOLVES[solver](session, web, 5)
    assert session.stats.plans_computed == 1
    assert res2.cache_hit


COUNTED = {"solver.solve", "solver.setup", "solver.count_nnz", "session.fingerprint",
           "solver.iterate"}
USAGE = {"user_s", "sys_s", "minflt", "majflt", "nvcsw", "nivcsw"}


def test_one_power_solve_is_one_span_tree(session, web):
    tracer = get_tracer()
    tracer.clear()
    power_iteration(session, web, tol=0.0, max_iters=4)
    spans = tracer.spans()
    by_id = {s["id"]: s for s in spans}

    def kids(span):
        return sorted(s["name"] for s in spans if s["parent"] == span["id"])

    (root,) = [s for s in spans if s["parent"] is None]
    assert root["name"] == "solver.solve"
    assert root["attrs"]["solver"] == "power" and root["attrs"]["max_iters"] == 4
    assert all(s["root"] == root["id"] for s in spans)
    assert kids(root) == ["solver.iterate"] * 4 + ["solver.setup"]
    (setup,) = [s for s in spans if s["name"] == "solver.setup"]
    assert kids(setup) == ["session.serve", "solver.count_nnz"]
    (serve,) = [s for s in spans if s["name"] == "session.serve"]
    assert kids(serve) == ["session.fingerprint", "session.optimize"]
    (fingerprint,) = [s for s in spans if s["name"] == "session.fingerprint"]
    assert fingerprint["attrs"]["bytes"] == web.astype(np.float32).nbytes
    for it in (s for s in spans if s["name"] == "solver.iterate"):
        assert kids(it) == ["kernel.execute"]
    # the set-up comes first, then the iterations in order
    assert by_id[setup["parent"]] is root
    starts = [s["ts"] for s in sorted(spans, key=lambda s: s["id"]) if s["parent"] == root["id"]]
    assert starts == sorted(starts)


class _CsrPredictor(_FakePredictor):
    def predict_format(self, feats, objective):
        return "csr"

    def estimate_objective(self, feats, config, objective):
        return 0.5 if config.fmt == "csr" else 1.0


def test_power_solve_on_a_csr_plan_gathers_x_in_the_kernel(web):
    """Every ``kernel.execute`` of a CSR solve says where x was gathered."""
    session = AutoSpmvSession(AutoSpMV(_CsrPredictor(), _FakeOverhead()))
    tracer = get_tracer()
    tracer.clear()
    res = power_iteration(session, web, tol=0.0, max_iters=3)
    assert res.iterations == 3
    execute = [s for s in tracer.spans() if s["name"] == "kernel.execute"]
    assert [s["attrs"] for s in execute] == [{"fmt": "csr", "gather": "vmem"}] * 3


def test_thread_usage_rides_on_the_five_counted_spans_only(session, web):
    tracer = get_tracer()
    tracer.clear()
    power_iteration(session, web, tol=0.0, max_iters=3)
    spans = tracer.spans()
    assert COUNTED <= {s["name"] for s in spans}
    for s in spans:
        attrs = s.get("attrs") or {}
        if s["name"] in COUNTED:
            assert USAGE <= set(attrs), s["name"]
            assert all(attrs[k] >= 0 for k in USAGE), (s["name"], attrs)
        else:
            assert not USAGE & set(attrs), s["name"]
    (root,) = [s for s in spans if s["name"] == "solver.solve"]
    iterate = [s for s in spans if s["name"] == "solver.iterate"]
    # the root's usage holds its iterations'
    assert root["attrs"]["minflt"] >= sum(s["attrs"]["minflt"] for s in iterate)


def test_force_fp32_guard_recompiles_bf16_schedules(web):
    bf16 = KernelSchedule(accum_dtype="bfloat16")
    session = _session(schedule=bf16)
    driver = IterativeSolver(session, web, name="guard")
    plan = driver.setup()
    assert plan.schedule.accum_dtype == "bfloat16"
    assert driver._spmv_kernel.schedule.accum_dtype == "float32"
    # and the iteration results are fp32-grade
    x = np.random.default_rng(1).standard_normal(web.shape[1]).astype(np.float32)
    y = driver.matvec(x)
    ref = web.astype(np.float64) @ x.astype(np.float64)
    assert np.abs(y - ref).max() / (np.abs(ref).max() + 1e-9) < 1e-5
