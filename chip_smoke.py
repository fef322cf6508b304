#!/usr/bin/env python3
"""Chip smoke test: drive the Auto-SpMV serving path once on a TPU.

Run from the root of a checkout (one process, no subprocesses; all data
comes from seeds):

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # four chips: the sharded executor only

One chip: builds the tuner as ``python -m repro.launch.serve --spmv`` does,
generates ``human_gene2`` at its published size (n = 14,340, ~9.0M nnz),
serves requests through ``SpmvServer`` in compile-time mode (batch path,
CSR) and in run-time mode (session with the format bandit, as ``--adaptive``
builds it), then runs every seed format through ``compile_spmv``. A format
whose storage ``human_gene2`` cannot hold (``InfeasibleConfig``) runs on
``pkustk04`` at scale 0.25 instead; CSR runs on both. Four chips: the
``shard_partitioned`` executor over 4 devices on ``human_gene2``, compared
with the float64 reference and the one-chip ELL kernel.

Every result is checked against a float64 numpy reference. Timings printed
here are smoke timings (first call includes compilation), not metrics.
With no TPU the script exits non-zero and prints no result line; the last
line of stdout on success is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SEED = 0
SERVE_REQUESTS = 3  # per serving mode; requests repeat the matrix
BIG = ("human_gene2", 1.0)
FALLBACK = ("pkustk04", 0.25)
MAX_ELEMS = 210_000_000  # 14,340^2 is just over generate_dense's default cap
MOSAIC_MARK = "tpu_custom_call"  # a Mosaic kernel in the lowered program


class SmokeFailure(RuntimeError):
    pass


def say(*parts) -> None:
    print(*parts, flush=True)


def reference(dense, x):
    """float64 ``dense @ x``, in row chunks to bound host memory."""
    import numpy as np

    x64 = x.astype(np.float64)
    out = np.empty(dense.shape[0], dtype=np.float64)
    for r0 in range(0, dense.shape[0], 2048):
        out[r0 : r0 + 2048] = dense[r0 : r0 + 2048].astype(np.float64) @ x64
    return out


def rel_error(y, ref) -> float:
    import numpy as np

    return float(np.abs(np.asarray(y, np.float64) - ref).max() / np.abs(ref).max())


def check(label: str, y, ref, accum_dtype: str) -> float:
    tol = 3e-2 if accum_dtype == "bfloat16" else 1e-5
    err = rel_error(y, ref)
    ok = err <= tol
    say(f"  {label}: rel.err {err:.3e} (tol {tol:g}) {'PASS' if ok else 'FAIL'}")
    if not ok:
        raise SmokeFailure(f"{label}: rel.err {err:.3e} > {tol:g}")
    return err


def load(name: str, scale: float, rng):
    import numpy as np

    from repro.sparse.generate import generate_by_name

    t0 = time.perf_counter()
    dense = generate_by_name(name, scale=scale, max_elems=MAX_ELEMS)
    x = rng.normal(size=dense.shape[1]).astype(np.float32)
    ref = reference(dense, x)
    nnz = int(np.count_nonzero(dense))
    say(f"matrix {name}@{scale}: n={dense.shape[0]} nnz={nnz} "
        f"(generated + float64 reference in {time.perf_counter() - t0:.1f}s)")
    return dense, x, ref


def serve_phase(tuner, dense, x, ref) -> None:
    """Compile-time (batch, CSR) and run-time (bandit) serving."""
    from repro.core.session import AutoSpmvSession
    from repro.telemetry import AdaptiveFormatSelector, TelemetryRecorder
    from repro.train.serve import SpmvRequest, SpmvServer

    modes = {
        "compile-time": AutoSpmvSession(tuner),
        "run-time": AutoSpmvSession(
            tuner, telemetry=TelemetryRecorder(), adaptive=AdaptiveFormatSelector()
        ),
    }
    for mode, session in modes.items():
        server = SpmvServer(session)
        reqs = [SpmvRequest(rid=i, dense=dense, x=x) for i in range(SERVE_REQUESTS)]
        t0 = time.perf_counter()
        done = server.run(reqs)
        say(f"serve {mode}: {len(done)} requests in {time.perf_counter() - t0:.2f}s, "
            f"session {session.stats.as_dict()}")
        for r in done:
            check(f"serve {mode} req {r.rid} fmt={r.fmt or 'csr'} hit={r.cache_hit}",
                  r.y, ref, r.schedule.accum_dtype)
        if len(done) != SERVE_REQUESTS:
            raise SmokeFailure(f"serve {mode}: {len(done)} of {SERVE_REQUESTS} answered")
        if session.stats.plans_computed >= session.stats.requests:
            raise SmokeFailure(f"serve {mode}: no plan was reused")


def timed_call(fn, x):
    import jax

    t0 = time.perf_counter()
    y = jax.block_until_ready(fn(x))
    return y, time.perf_counter() - t0


def format_phase(matrices) -> None:
    """Every seed format through ``compile_spmv``, compiled by Mosaic."""
    import jax

    from repro.kernels.common import DEFAULT_SCHEDULE, InfeasibleConfig
    from repro.kernels.ops import compile_spmv
    from repro.sparse.registry import format_names, get_format

    sched = DEFAULT_SCHEDULE
    ran: dict[str, list[str]] = {}
    for fmt in format_names():
        spec = get_format(fmt)
        for name, (dense, x, ref) in matrices.items():
            if ran.get(fmt) and fmt != "csr":
                break
            try:
                t0 = time.perf_counter()
                kernel = compile_spmv(dense, fmt, sched)
                prep_s = time.perf_counter() - t0
            except InfeasibleConfig as exc:
                say(f"format {fmt} on {name}: infeasible ({exc})")
                continue
            text = jax.jit(lambda m, v: spec.spmv(m, v, sched)).lower(
                kernel.mat, x
            ).as_text()
            if MOSAIC_MARK not in text:
                raise SmokeFailure(f"format {fmt}: no Mosaic kernel in the program")
            y, first_s = timed_call(kernel, x)
            _, warm_s = timed_call(kernel, x)
            say(f"format {fmt} on {name}: prepare {prep_s:.2f}s, first call "
                f"{first_s * 1e3:.2f} ms, warm call {warm_s * 1e3:.3f} ms "
                "(smoke timings)")
            check(f"format {fmt} on {name}", y, ref, sched.accum_dtype)
            ran.setdefault(fmt, []).append(name)
            del kernel
    missing = [f for f in format_names() if not ran.get(f)]
    if missing or len(ran.get("csr", [])) != len(matrices):
        raise SmokeFailure(f"formats not run: {missing}; csr ran on {ran.get('csr')}")


def one_chip(rng) -> None:
    from repro.core.session import build_tuner
    from repro.sparse.generate import MATRIX_NAMES

    t0 = time.perf_counter()
    tuner = build_tuner(scale=0.0015, names=MATRIX_NAMES[:8])  # launch.serve defaults
    say(f"tuner ready in {time.perf_counter() - t0:.1f}s")
    big = load(*BIG, rng)
    serve_phase(tuner, *big)
    fallback = load(*FALLBACK, rng)
    format_phase({BIG[0]: big, FALLBACK[0]: fallback})


def four_chips(rng) -> None:
    import jax
    import numpy as np

    from repro.kernels.common import DEFAULT_SCHEDULE
    from repro.kernels.ops import compile_spmv
    from repro.partition.executor import shard_partitioned
    from repro.partition.partitioner import partition_rows

    if len(jax.devices()) < 4:
        raise SmokeFailure(f"--four-chips needs 4 devices, found {len(jax.devices())}")
    dense, x, ref = load(*BIG, rng)
    one = compile_spmv(dense, "ell", DEFAULT_SCHEDULE)
    y_one, _ = timed_call(one, x)
    check("one-chip ell", y_one, ref, DEFAULT_SCHEDULE.accum_dtype)
    t0 = time.perf_counter()
    sharded = shard_partitioned(dense, partition_rows(dense, 4), schedule=DEFAULT_SCHEDULE)
    say(f"sharded executor built in {time.perf_counter() - t0:.2f}s, "
        f"padded rows per block {sharded.padded_rows}")
    devices = {s.device for s in sharded.data.addressable_shards}
    say(f"  planes on {len(devices)} device(s): "
        f"{sorted(str(d) for d in devices)}")
    if len(devices) != 4:
        raise SmokeFailure(f"planes landed on {len(devices)} device(s), not 4")
    y, first_s = timed_call(sharded, x)
    _, warm_s = timed_call(sharded, x)
    say(f"sharded ell over 4 chips: first call {first_s * 1e3:.2f} ms, warm call "
        f"{warm_s * 1e3:.3f} ms (smoke timings)")
    check("sharded ell vs float64", y, ref, DEFAULT_SCHEDULE.accum_dtype)
    diff = rel_error(y, np.asarray(y_one, np.float64))
    say(f"  sharded ell vs one-chip ell: rel.diff {diff:.3e}")
    if diff > 1e-5:
        raise SmokeFailure(f"sharded and one-chip ell differ by {diff:.3e}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded executor over 4 chips")
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r}); "
              "this smoke runs only on a chip", file=sys.stderr)
        return 2
    say(f"device kind {dev.device_kind!r}, {len(devices)} device(s), "
        f"jax {jax.__version__}")

    from repro.kernels.common import default_interpret
    from repro.utils.compile_cache import configure_compile_cache

    say(f"compile cache: {configure_compile_cache()}")
    if default_interpret():
        raise SmokeFailure("kernels would run in interpret mode on a TPU")
    rng = np.random.default_rng(SEED)
    (four_chips if args.four_chips else one_chip)(rng)
    say(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices)},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
