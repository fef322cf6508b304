"""Bench-regression gate: compare a fresh ``BENCH_<label>.json`` against the
committed baseline (``benchmarks/baseline/BENCH_smoke.json``).

Absolute wall times on shared CI runners are too noisy to gate on, so every
gate compares a *within-run latency ratio*: both sides of each ratio come
from the same process on the same machine, so runner speed cancels. Gated
ratios (lower = better):

* ``solver_adaptive_vs_always`` — the solvers bench's per-iteration p50
  with the adaptive SpMV↔SpMSpV policy over the always-SpMV run;
* ``lm_sparse_per_token`` — sparse-served decode over dense decode;
* ``obs_overhead`` — warm serving with the observability layer on over the
  same path with it disabled.

Every check is evaluated and reported (``PASS``/``FAIL`` per line) before
the process exits nonzero — one regression never masks another in CI logs;
the final summary counts the failures by name.

A gate fails when its current ratio is more than ``--threshold`` (default
25%) worse than the baseline ratio AND the ratio has left the gate's
absolute comfort zone (``max_ok_ratio`` — e.g. the adaptive solver is
more than 25% slower per iteration than always-SpMV). The absolute guard
keeps ratio jitter that is still well inside the comfort zone from failing
CI.

A baseline that lacks a metric a gate references is itself a failure with
an explicit message naming the bench and metric — a silently skipped gate
is how regressions ship.

Also asserts every benchmark the baseline ran still exists and passed.

CLI::

    python -m benchmarks.compare artifacts/bench/BENCH_smoke.json \
        --baseline benchmarks/baseline/BENCH_smoke.json
"""

from __future__ import annotations

import argparse
import json
from dataclasses import dataclass
from pathlib import Path

from repro.utils.logging import get_logger

log = get_logger("bench.compare")

DEFAULT_BASELINE = Path(__file__).parent / "baseline" / "BENCH_smoke.json"

@dataclass(frozen=True)
class RatioGate:
    """One gated within-run latency ratio (numerator / denominator)."""

    name: str
    bench: str  # benchmark record the metrics live in
    num_key: str
    den_key: str
    max_ok_ratio: float  # absolute comfort zone: never fail at or below this

    def keys(self) -> tuple[str, str]:
        return (self.num_key, self.den_key)


GATES = (
    RatioGate(
        name="solver_adaptive_vs_always",
        bench="solvers",
        num_key="adaptive/iter_p50_s",
        den_key="always/iter_p50_s",
        # adaptive routing must stay within 25% of always-SpMV per
        # iteration: the two runs execute mostly-identical SpMV work, so
        # their p50 ratio hovers near 1.0 with interpret-mode jitter either
        # side — only a structural slowdown pushes it past this
        max_ok_ratio=1.25,
    ),
    RatioGate(
        name="lm_sparse_per_token",
        bench="sparse_lm",
        num_key="sparse/per_token_s",
        den_key="dense/per_token_s",
        # sparse-served decode over dense decode on the same pruned params
        # (both warmed). The shared dense prefill dominates these tiny serving
        # runs, so the ratio measured on this container hovers near 1.0; the
        # guard bounds a structural blowup of the SpMV route (e.g. plans
        # recomputed per tick), not interpret-mode jitter
        max_ok_ratio=3.0,
    ),
    RatioGate(
        name="obs_overhead",
        bench="obs_overhead",
        num_key="obs_on/per_request_s",
        den_key="obs_off/per_request_s",
        # the observability layer (spans + counters + energy cells + burn
        # windows) over the identical warm serve path with obs disabled; the
        # layer claims a no-op fast path, so it must never double the
        # per-request cost
        max_ok_ratio=2.0,
    ),
)


def _bench_metrics(report: dict, name: str) -> dict | None:
    for bench in report.get("benchmarks", ()):
        if bench.get("name") == name:
            return bench.get("metrics") or {}
    return None


def gate_ratio(report: dict, gate: RatioGate) -> tuple[float | None, str | None]:
    """(ratio, problem): the gate's ratio in ``report``, or why it's absent.

    The problem string names the bench and metric precisely — it becomes
    the failure message when the *baseline* is the side missing it."""
    metrics = _bench_metrics(report, gate.bench)
    if metrics is None:
        return None, f"bench {gate.bench!r} not present"
    for key in gate.keys():
        if key not in metrics:
            return None, f"bench {gate.bench!r} lacks metric {key!r}"
    num, den = float(metrics[gate.num_key]), float(metrics[gate.den_key])
    if den <= 0 or num <= 0:
        return None, (
            f"bench {gate.bench!r} metric {gate.num_key!r}/{gate.den_key!r} "
            f"non-positive ({num:g}/{den:g})"
        )
    return num / den, None


@dataclass(frozen=True)
class Outcome:
    """One checked thing (gate or bench-presence) and how it went.

    Every outcome is evaluated and reported even after a failure — one
    regression must never mask another in the CI log."""

    name: str
    ok: bool
    detail: str


def compare(
    current: dict,
    baseline: dict,
    *,
    threshold: float = 0.25,
) -> tuple[bool, list[Outcome]]:
    """Evaluate every check; returns (all ok, one outcome per check)."""
    outcomes: list[Outcome] = []

    base_names = {b.get("name") for b in baseline.get("benchmarks", ())}
    cur_by_name = {b.get("name"): b for b in current.get("benchmarks", ())}
    for name in sorted(base_names):
        bench = cur_by_name.get(name)
        if bench is None:
            outcomes.append(Outcome(
                f"bench:{name}", False, f"baseline bench {name!r} was not run"
            ))
        elif not bench.get("ok"):
            outcomes.append(Outcome(
                f"bench:{name}", False, f"bench {name!r} did not pass"
            ))
        else:
            outcomes.append(Outcome(f"bench:{name}", True, "ran and passed"))

    for gate in GATES:
        base_ratio, base_problem = gate_ratio(baseline, gate)
        cur_ratio, cur_problem = gate_ratio(current, gate)
        if base_ratio is None:
            # a gate the baseline cannot anchor is a hard failure: regenerate
            # the committed baseline (benchmarks/baseline/BENCH_smoke.json)
            # with the current bench set instead of silently skipping
            outcomes.append(Outcome(
                gate.name, False,
                f"baseline missing metric: {base_problem}; regenerate the "
                f"committed baseline to include {gate.num_key!r} and "
                f"{gate.den_key!r}",
            ))
            continue
        if cur_ratio is None:
            outcomes.append(Outcome(
                gate.name, False,
                f"current run lost the measurement ({cur_problem})",
            ))
            continue
        rel = cur_ratio / base_ratio - 1.0
        detail = (
            f"ratio {cur_ratio:.4g} vs baseline {base_ratio:.4g} ({rel:+.1%})"
        )
        if rel > threshold and cur_ratio > gate.max_ok_ratio:
            outcomes.append(Outcome(
                gate.name, False,
                f"{detail}: degraded > {threshold:.0%} and exceeds the "
                f"absolute guard {gate.max_ok_ratio:g}",
            ))
        elif rel > threshold:
            outcomes.append(Outcome(
                gate.name, True,
                f"{detail}: degraded but still inside the absolute comfort "
                f"zone ({cur_ratio:.4g} <= {gate.max_ok_ratio:g}); treated "
                f"as noise",
            ))
        else:
            outcomes.append(Outcome(gate.name, True, detail))
    return all(o.ok for o in outcomes), outcomes


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("results", help="fresh BENCH_<label>.json to check")
    ap.add_argument("--baseline", default=str(DEFAULT_BASELINE),
                    help="committed baseline results file")
    ap.add_argument("--threshold", type=float, default=0.25,
                    help="max relative ratio degradation before failing")
    args = ap.parse_args(argv)

    current = json.loads(Path(args.results).read_text())
    baseline = json.loads(Path(args.baseline).read_text())
    ok, outcomes = compare(current, baseline, threshold=args.threshold)
    for o in outcomes:  # every outcome, pass or fail, before any exit
        (log.info if o.ok else log.error)(
            "%s [%s]: %s", "PASS" if o.ok else "FAIL", o.name, o.detail
        )
    failed = [o.name for o in outcomes if not o.ok]
    if failed:
        log.error(
            "bench regression gate: FAIL (%d of %d checks): %s",
            len(failed), len(outcomes), ", ".join(failed),
        )
        return 1
    log.info("bench regression gate: PASS (%d checks)", len(outcomes))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
