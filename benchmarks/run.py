"""Benchmark orchestrator: one module per paper table/figure + the roofline
report. ``python -m benchmarks.run [--scale ci|paper] [--only fig9,table5]``.

``--smoke`` is the sub-minute CI tier: only the benches tagged smoke-capable
(the session-cache, adaptive-telemetry, partition, and format-sweep ones,
which skip dataset-wide predictor sweeps) at the smallest scale.

Every run also writes a machine-readable ``BENCH_<label>.json`` next to the
other artifacts (``artifacts/bench/`` by default): one record per executed
benchmark with its name, scale, duration, and the numeric metrics flattened
out of the payload its ``run()`` returned. The label comes from ``--label``,
the ``BENCH_LABEL`` environment variable, or the current git short sha (CI
passes ``--label smoke``, so the artifact name is stable across PRs). CI
runs the smoke tier, uploads the artifact, and gates on
``benchmarks/compare.py`` against the committed baseline — the bench
trajectory is a queryable, regression-checked time series instead of log
text.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import time
import traceback

from repro.utils.compile_cache import configure_compile_cache
from repro.utils.logging import get_logger

log = get_logger("bench.run")

BENCHES = [
    ("fig3", "benchmarks.fig3_default_vs_auto", "Fig.3 default vs Auto-SpMV (consph)"),
    ("fig4", "benchmarks.fig4_ablation", "Fig.4 per-knob ablation (eu-2005)"),
    ("fig9", "benchmarks.fig9_compile_time", "Fig.9 compile-time-mode gains"),
    ("fig10", "benchmarks.fig10_runtime_format", "Fig.10 run-time format gains"),
    ("table5", "benchmarks.table5_classification", "Table 5 knob classifiers"),
    ("table6", "benchmarks.table6_comparison", "Table 6 vs prior-work proxies"),
    ("fig11", "benchmarks.fig11_regression", "Fig.11 objective regressors"),
    ("table7", "benchmarks.table7_overhead", "Table 7 + Fig.6 overheads"),
    ("session_cache", "benchmarks.bench_session_cache", "Session cache cold vs warm"),
    ("adaptive", "benchmarks.bench_adaptive", "Telemetry bandit misprediction recovery"),
    ("partition", "benchmarks.bench_partition", "Partitioned vs monolithic SpMV"),
    ("solvers", "benchmarks.bench_solvers", "Iterative solvers + adaptive SpMSpV"),
    ("sparse_lm", "benchmarks.bench_sparse_lm", "Sparse LM serving vs dense decode"),
    ("obs_overhead", "benchmarks.bench_obs_overhead",
     "Observability overhead + SLO escalation loop"),
    ("fig12", "benchmarks.fig12_sensitivity", "Fig.12 hardware sensitivity"),
    ("roofline", "benchmarks.roofline", "Roofline report (dry-run artifacts)"),
    # keep last: activates the bcsr plugin, which widens the registry for the
    # rest of the process
    ("formats", "benchmarks.bench_formats", "Registered-format sweep incl. bcsr plugin"),
]

SMOKE_BENCHES = (
    "session_cache", "adaptive", "partition", "solvers", "sparse_lm",
    "obs_overhead", "formats",
)

_MAX_METRICS = 400  # per bench: keep the artifact readable, not exhaustive


def default_label() -> str:
    """Artifact label when ``--label`` is omitted: env var, then git sha."""
    env = os.environ.get("BENCH_LABEL", "").strip()
    if env:
        return env
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
        ).stdout.strip()
        if sha:
            return sha
    except OSError:
        pass
    return "local"


def results_file(label: str) -> str:
    return f"BENCH_{label}.json"


def _numeric_metrics(payload, prefix: str = "", out: dict | None = None) -> dict:
    """Flatten a bench payload into "path/to/leaf" -> number entries.

    Non-numeric leaves are dropped; non-string keys (some benches key on
    tuples) are stringified. Bounded so a dataset-sized payload cannot bloat
    the artifact.
    """
    if out is None:
        out = {}
    if len(out) >= _MAX_METRICS:
        return out
    if isinstance(payload, bool):
        out[prefix] = int(payload)
    elif isinstance(payload, (int, float)) and not isinstance(payload, bool):
        out[prefix] = float(payload)
    elif isinstance(payload, dict):
        for k, v in payload.items():
            key = f"{prefix}/{k}" if prefix else str(k)
            _numeric_metrics(v, key, out)
    elif isinstance(payload, (list, tuple)):
        for i, v in enumerate(payload):
            _numeric_metrics(v, f"{prefix}/{i}" if prefix else str(i), out)
    return out


def write_results(
    records: list[dict], scale: str, total_s: float, label: str | None = None
) -> str:
    from benchmarks.common import ART

    label = label or default_label()
    ART.mkdir(parents=True, exist_ok=True)
    path = ART / results_file(label)
    path.write_text(
        json.dumps(
            {
                "label": label,
                "scale": scale,
                "total_s": total_s,
                "benchmarks": records,
            },
            indent=1,
            default=float,
        )
    )
    return str(path)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--scale", choices=["smoke", "ci", "paper"], default="paper")
    ap.add_argument("--only", default=None, help="comma-separated bench names")
    ap.add_argument("--smoke", action="store_true",
                    help="sub-minute tier: smoke benches at the smallest scale")
    ap.add_argument("--label", default=None,
                    help="results-artifact label: BENCH_<label>.json "
                         "(default: $BENCH_LABEL, then the git short sha)")
    args = ap.parse_args(argv)
    configure_compile_cache()
    scale = "smoke" if args.smoke else args.scale
    if args.only:
        only = set(args.only.split(","))
    elif args.smoke:
        only = set(SMOKE_BENCHES)
    else:
        only = None

    failures, records = [], []
    t_all = time.time()
    for name, module, title in BENCHES:
        if only and name not in only:
            continue
        log.info("[%s] %s", name, title)
        t0 = time.time()
        record = {"name": name, "title": title, "scale": scale}
        try:
            import importlib

            mod = importlib.import_module(module)
            payload = mod.run(scale)
            record["ok"] = True
            record["metrics"] = _numeric_metrics(payload) if payload else {}
            log.info("[%s] done in %.1fs", name, time.time() - t0)
        except Exception:
            traceback.print_exc()
            failures.append(name)
            record["ok"] = False
            record["error"] = traceback.format_exc(limit=3)
        record["duration_s"] = time.time() - t0
        records.append(record)
    total_s = time.time() - t_all
    results_path = write_results(records, scale, total_s, args.label)
    log.info(
        "all benchmarks finished in %.1fs; results -> %s", total_s, results_path
    )
    if failures:
        log.error("FAILED: %s", failures)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
