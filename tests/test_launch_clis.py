"""Coverage for the launch CLIs (train/serve) on the host mesh — the same
entry points a fleet run uses, at reduced scale."""

import numpy as np
import pytest

from repro.launch.serve import main as serve_main
from repro.launch.train import main as train_main


def test_train_cli_runs_and_improves(tmp_path):
    trainer = train_main([
        "--arch", "qwen3-0.6b",
        "--steps", "4",
        "--seq-len", "32",
        "--batch", "2",
        "--ckpt-dir", str(tmp_path),
        "--ckpt-every", "4",
    ])
    losses = [h["loss"] for h in trainer.history]
    assert len(losses) == 4 and all(np.isfinite(losses))
    assert trainer.ckpt.latest_step() == 4


def test_train_cli_moe_with_dispatch_override(tmp_path):
    trainer = train_main([
        "--arch", "deepseek-moe-16b",
        "--steps", "2",
        "--seq-len", "32",
        "--batch", "2",
        "--dispatch-format", "sell",
        "--ckpt-dir", str(tmp_path),
    ])
    assert trainer.cfg.dispatch_format == "sell"
    assert len(trainer.history) == 2


def test_serve_cli_generates():
    done = serve_main([
        "--arch", "llama3-8b",
        "--requests", "2",
        "--slots", "2",
        "--max-new-tokens", "3",
        "--max-len", "64",
    ])
    assert all(r.done and len(r.generated) == 3 for r in done)


def test_serve_cli_spmv_adaptive_telemetry(tmp_path):
    """SpMV serving with the full telemetry loop switched on: requests are
    answered correctly, the tuning cache and telemetry log are persisted."""
    done = serve_main([
        "--spmv",
        "--requests", "6",
        "--spmv-train-matrices", "2",
        "--spmv-scale", "0.001",
        "--spmv-cache", str(tmp_path / "tuning.json"),
        "--adaptive",
        "--telemetry-log", str(tmp_path / "telemetry.jsonl"),
        "--refit-every", "4",
    ])
    assert len(done) == 6
    for r in done:
        ref = r.dense @ r.x
        err = np.abs(r.y - ref).max() / (np.abs(ref).max() + 1e-9)
        assert err < 0.05  # bfloat16 schedules allowed; must still be SpMV
        assert r.fmt is not None and r.latency_s > 0
    assert (tmp_path / "tuning.json").exists()
    log_lines = (tmp_path / "telemetry.jsonl").read_text().splitlines()
    assert len(log_lines) == 6


def test_serve_cli_spmv_partitioned(tmp_path):
    """SpMV serving with composite partitioned plans: outputs stay correct
    and the per-request format reports the per-block routing."""
    done = serve_main([
        "--spmv",
        "--requests", "4",
        "--spmv-train-matrices", "2",
        "--spmv-scale", "0.001",
        "--spmv-cache", str(tmp_path / "tuning.json"),
        "--partition",
        "--max-blocks", "4",
    ])
    assert len(done) == 4
    for r in done:
        ref = r.dense @ r.x
        err = np.abs(r.y - ref).max() / (np.abs(ref).max() + 1e-9)
        assert err < 0.05  # bfloat16 schedules allowed; must still be SpMV
        assert r.fmt and r.latency_s > 0  # "fmtA+fmtB..." composite report
    assert (tmp_path / "tuning.json").exists()


def test_solve_cli_profile_dir_captures_the_solves_spans(tmp_path):
    """``launch.solve --profile-dir``: one capture holds the solve's spans."""
    import glob

    import jax

    from repro.launch.solve import main as solve_main

    res = solve_main([
        "--solver", "power",
        "--matrix", "fem",
        "--scale", "0.0008",
        "--max-iters", "3",
        "--tol", "0",
        "--train-matrices", "2",
        "--json-out", str(tmp_path / "solve.json"),
        "--profile-dir", str(tmp_path / "profile"),
    ])
    assert res.iterations == 3
    (path,) = glob.glob(str(tmp_path / "profile" / "**" / "*.xplane.pb"), recursive=True)
    profile = jax.profiler.ProfileData.from_file(path)
    names = [
        e.name
        for plane in profile.planes if plane.name.startswith("/host:")
        for line in plane.lines
        for e in line.events
    ]
    assert names.count("solver.solve") == 1
    assert names.count("solver.iterate") == names.count("kernel.execute") == 3
    assert {"solver.setup", "solver.count_nnz", "session.serve",
            "session.fingerprint"} <= set(names)


def test_solve_cli_cg_on_the_hpcg_stencil_takes_no_dense_path(tmp_path, monkeypatch):
    """``launch.solve --solver cg --matrix hpcg``: the stencil goes to CG as
    a host CSR, which refuses to be made dense, and is never symmetrized."""
    import json

    from repro.launch import solve as solve_cli
    from repro.launch.solve import main as solve_main
    from repro.sparse.generate import hpcg_stencil

    def densify(dense):
        raise AssertionError("the stencil is SPD already: spd_operator must not run")

    monkeypatch.setattr(solve_cli, "spd_operator", densify)
    out = tmp_path / "solve.json"
    res = solve_main([
        "--solver", "cg",
        "--matrix", "hpcg",
        "--grid", "5", "4", "3",
        "--max-iters", "40",
        "--tol", "0",
        "--train-matrices", "2",
        "--json-out", str(out),
    ])
    payload = json.loads(out.read_text())
    assert payload["n"] == 60 and payload["nnz"] == 13 * 10 * 7
    assert payload["iterations"] == 40 and payload["fmt"] == "csr"
    # toward the float64 solution of the same system
    mat = hpcg_stencil(5, 4, 3)
    dense = np.zeros(mat.shape)
    np.add.at(dense, (mat.row_ids(), mat.indices), mat.data)
    b = np.random.default_rng(0).standard_normal(60).astype(np.float32)
    want = np.linalg.solve(dense, b.astype(np.float64))
    assert np.abs(res.value - want).max() / np.abs(want).max() < 1e-5
    assert payload["residual"] < 1e-6


def test_solve_cli_grid_and_scale_size_the_stencil():
    from repro.launch.solve import stencil_grid

    assert stencil_grid([7], 1.0) == (7, 7, 7)
    assert stencil_grid([5, 4, 3], 1.0) == (5, 4, 3)
    assert stencil_grid(None, 1.0) == (104, 104, 104)
    assert stencil_grid(None, 0.001) == (10, 10, 10)
    with pytest.raises(SystemExit, match="--grid"):
        stencil_grid([4, 4], 1.0)
