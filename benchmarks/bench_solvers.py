"""Iterative solvers over the serving stack: amortization + adaptive SpMSpV.

The acceptance study for ``repro.solvers``:

- **PageRank** on the ``webgraph`` suite matrix must match the dense-NumPy
  reference ranks to 1e-5 while computing exactly ONE ``serve_optimize``
  plan for the whole solve (the §5.3 amortize-forever claim, counted);
- **CG** on an SPD fem operator must match ``np.linalg.solve`` to 1e-5
  with monotonically trending-down residuals;
- **adaptive SpMV↔SpMSpV** power iteration from a single seed vertex must
  beat the always-SpMV run on *total modeled work* (stored nonzeros
  touched) — the sparse-frontier iterations are the entire point of the
  SpMSpV path, and modeled work is deterministic where wall time is not.

Reported metrics include end-to-end solve latency, per-iteration p50, and
the adaptive/always per-iteration latency ratio — a gated metric in
``benchmarks/compare.py`` (both sides measured in the same process, so the
ratio cancels runner speed).
"""

from __future__ import annotations

import numpy as np

from benchmarks.common import print_table, save_result
from repro.core.session import AutoSpmvSession, build_tuner
from repro.solvers import AdaptiveSpmvPolicy, cg, pagerank, power_iteration
from repro.solvers.pagerank import pagerank_reference
from repro.sparse.generate import MATRIX_NAMES, generate_by_name, random_matrix
from repro.telemetry import AdaptiveFormatSelector
from repro.utils.logging import get_logger

log = get_logger("bench.solvers")

SCALES = {
    "smoke": dict(web_scale=0.0003, fem_n=192, train_scale=0.0008,
                  train_names=4, pr_tol=1e-6, pr_iters=120, power_iters=12),
    "ci": dict(web_scale=0.0005, fem_n=256, train_scale=0.0012,
               train_names=8, pr_tol=1e-7, pr_iters=160, power_iters=16),
    "paper": dict(web_scale=0.001, fem_n=384, train_scale=0.0015,
                  train_names=12, pr_tol=1e-8, pr_iters=200, power_iters=20),
}


def spd_fem(n: int, seed: int = 3) -> np.ndarray:
    """Symmetrized diagonally-dominant fem matrix — CG's SPD contract."""
    F = random_matrix(n, 6.0, "fem", seed=seed).astype(np.float32)
    S = (F + F.T) / 2
    margin = float(np.abs(S).sum(axis=1).max()) + 1.0
    return (S + margin * np.eye(n, dtype=np.float32)).astype(np.float32)


def run(scale: str = "ci") -> dict:
    cfg = SCALES.get(scale, SCALES["ci"])
    tuner = build_tuner(
        scale=cfg["train_scale"],
        names=MATRIX_NAMES[: cfg["train_names"]],
        n_extra=0,
        fit_overhead=False,
    )
    session = AutoSpmvSession(tuner)
    out: dict = {"scale": scale}

    # --- PageRank on webgraph: reference ranks + one-plan amortization ----
    web = generate_by_name("webgraph", scale=cfg["web_scale"])
    plans_before = session.stats.plans_computed
    pr = pagerank(session, web, tol=cfg["pr_tol"], max_iters=cfg["pr_iters"])
    plans_pr = session.stats.plans_computed - plans_before
    ref = pagerank_reference(web, tol=1e-12)
    pr_err = float(np.abs(pr.value - ref).max())
    out["pagerank"] = {
        "n": int(web.shape[0]),
        "nnz": int((web != 0).sum()),
        "iterations": pr.iterations,
        "converged": pr.converged,
        "residual": pr.residual,
        "total_s": float(sum(pr.iteration_seconds)),
        "iter_p50_s": pr.iter_p50_s(),
        "max_err_vs_reference": pr_err,
        "plans_computed": plans_pr,
        "dangling_nodes": pr.extras["dangling_nodes"],
    }
    assert pr.converged, f"pagerank failed to converge: residual {pr.residual}"
    assert pr_err < 1e-5, f"pagerank diverged from dense reference: {pr_err}"
    assert plans_pr == 1, f"pagerank computed {plans_pr} plans; expected 1"

    # --- CG on SPD fem: reference solution + decreasing residuals ---------
    S = spd_fem(cfg["fem_n"])
    b = np.random.default_rng(0).standard_normal(cfg["fem_n"]).astype(np.float32)
    res_cg = cg(session, S, b, tol=1e-10, max_iters=300)
    x_ref = np.linalg.solve(S.astype(np.float64), b.astype(np.float64))
    cg_err = float(np.abs(res_cg.value - x_ref).max())
    out["cg"] = {
        "n": cfg["fem_n"],
        "iterations": res_cg.iterations,
        "converged": res_cg.converged,
        "residual": res_cg.residual,
        "total_s": float(sum(res_cg.iteration_seconds)),
        "iter_p50_s": res_cg.iter_p50_s(),
        "max_err_vs_solve": cg_err,
    }
    assert res_cg.converged, f"cg failed to converge: residual {res_cg.residual}"
    assert cg_err < 1e-5, f"cg diverged from np.linalg.solve: {cg_err}"

    # --- adaptive SpMV<->SpMSpV vs always-SpMV (power, sparse seed) -------
    # same matrix, same seed vertex, same iteration count; only the routing
    # policy differs, so the modeled-work and latency deltas are the policy's
    k = cfg["power_iters"]
    adaptive = power_iteration(
        session, web, tol=0.0, max_iters=k,
        policy=AdaptiveSpmvPolicy(selector=AdaptiveFormatSelector()),
    )
    always = power_iteration(session, web, tol=0.0, max_iters=k)
    ratio = adaptive.iter_p50_s() / max(always.iter_p50_s(), 1e-12)
    out["adaptive"] = {
        "iterations": adaptive.iterations,
        "spmv_calls": adaptive.spmv_calls,
        "spmspv_calls": adaptive.spmspv_calls,
        "modeled_work": adaptive.modeled_work,
        "iter_p50_s": adaptive.iter_p50_s(),
        "total_s": float(sum(adaptive.iteration_seconds)),
    }
    out["always"] = {
        "iterations": always.iterations,
        "modeled_work": always.modeled_work,
        "iter_p50_s": always.iter_p50_s(),
        "total_s": float(sum(always.iteration_seconds)),
    }
    out["adaptive_over_always_iter_ratio"] = float(ratio)
    assert adaptive.spmspv_calls > 0, (
        "adaptive policy never routed a sparse frontier through SpMSpV"
    )
    assert adaptive.modeled_work < always.modeled_work, (
        f"adaptive modeled work {adaptive.modeled_work} did not beat "
        f"always-SpMV {always.modeled_work}"
    )

    print_table(
        "Iterative solvers over one served plan",
        ["solver", "iters", "converged", "residual", "iter p50 ms", "max err"],
        [
            ["pagerank", pr.iterations, pr.converged, pr.residual,
             pr.iter_p50_s() * 1e3, pr_err],
            ["cg", res_cg.iterations, res_cg.converged, res_cg.residual,
             res_cg.iter_p50_s() * 1e3, cg_err],
        ],
    )
    log.info(
        "adaptive power: %d spmspv + %d spmv calls, modeled work %d vs "
        "always-SpMV %d (%.1f%% saved); iter p50 ratio %.3f",
        adaptive.spmspv_calls,
        adaptive.spmv_calls,
        adaptive.modeled_work,
        always.modeled_work,
        100.0 * (1 - adaptive.modeled_work / always.modeled_work),
        ratio,
    )
    save_result("bench_solvers", out)
    return out


if __name__ == "__main__":
    run("ci")
