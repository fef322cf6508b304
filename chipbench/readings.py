"""Readings that the limits of ``chipbench/limits/<cell>.json`` are set from.

    python -m chipbench.readings --workload rim.solve --seeds 101-112 \
        --control-seeds 101-103 --seconds 30

In one process, on the chip: the program's sound runs, one per seed, each
the same set-up, window and check as a benchmark run (the tuner is built
once and shared), and the control on the first seeds: the driver's
reference at the precision below the configuration's, answering the first
requests in the program's place, through the same check and decision. The
lower reading of a number is the largest a sound run gives, its upper
reading the smallest the control gives. Prints one JSON line with both per
seed; the benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys

from chipbench import run

CONTROL_REQUESTS = 3  # per control seed, about half a run's requests


def seed_list(text: str) -> list[int]:
    """``"5-8,11"`` -> ``[5, 6, 7, 8, 11]``."""
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi or lo) + 1))
    return out


def control_readings(cell: run.Cell, seed: int, scale: float = 1.0) -> dict:
    """Worst numbers of the control over its first requests, and whether
    the run's decision reads it correct. The cell's driver makes the inputs
    the program would be handed, from the configuration and ``seed``."""
    driver = cell.driver
    inputs = driver.inputs(cell.config, seed, scale)
    answers = driver.control(inputs, cell.config, cell.traffic, seed, CONTROL_REQUESTS)
    worst, failed = run.decide(driver.check(inputs, answers, cell.traffic), cell.limits)
    return {"correct": failed == 0, **worst}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, type=seed_list)
    ap.add_argument("--control-seeds", required=True, type=seed_list)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    run.import_program()
    try:
        run.device_info(cell.chips)
    except run.NoChip as exc:
        print(f"chipbench: {exc}", file=sys.stderr)
        return 3
    from repro.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    tuner = cell.driver.build_tuner(cell.traffic)
    program = {}
    for seed in args.seeds:
        res = run.run_cell(args.workload, seed, args.seconds, False, tuner=tuner)
        program[seed] = {"requests": res["attempted"], "correct": res["correct"],
                         "spmv_ms": res["metrics"]["spmv_ms"]["value"],
                         **{k: c["value"] for k, c in res["checks"].items()}}
        print(json.dumps({"seed": seed, **program[seed]}), flush=True)
        gc.collect()
    control = {}
    for seed in args.control_seeds:
        control[seed] = control_readings(cell, seed)
        print(json.dumps({"control_seed": seed, **control[seed]}), flush=True)
        gc.collect()
    lower = {k: max(p[k] for p in program.values()) for k in cell.limits}
    upper = {k: min(c[k] for c in control.values()) for k in cell.limits}
    print(json.dumps({"workload": args.workload, "lower": lower, "upper": upper,
                      "program": program, "control": control}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
