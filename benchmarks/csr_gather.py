"""Where the CSR kernel should gather x: inside the kernel or in XLA.

    PYTHONPATH=src python -m benchmarks.csr_gather [--rows 64,128,192,512,1024,2048]
                                                   [--nnz-tiles 128,1024] [--tiles 4096]

Times ``csr_spmv_pallas`` on a TPU with each gather forced, over a random
stream of ``--tiles`` tiles (one 8-row block each, columns uniform over x)
for every x of R rows of 128 (n_cols = 128 R) and every tile width. The
in-kernel gather walks the R rows of x for each vreg of column ids, so its
time per tile grows linearly in R; XLA's gather costs a fixed time per
stream entry. Per tile width the script fits the in-kernel time per tile
to ``a + b R`` and reports the R at which it meets XLA's mean time per
tile: where ``kernels.csr.VMEM_GATHER_MAX_ROWS`` belongs. Take R in whole
steps of the walk (``kernels.csr.WALK_ROWS``), which x is padded to.
Each time is the median of ``--reps`` calls, host clock around
``block_until_ready``. Needs a TPU: exits 2 without one. Prints one JSON
line.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time

import numpy as np

RPB = 8


def stream(rng: np.random.Generator, n_cols: int, nt: int, tiles: int):
    """A tile-aligned CSR stream of ``tiles`` tiles, one row block each."""
    nnz = tiles * nt
    rows = np.repeat(np.arange(tiles, dtype=np.int32) * RPB, nt)
    rows += np.sort(rng.integers(0, RPB, (tiles, nt)), axis=1).reshape(-1).astype(np.int32)
    return (
        rng.normal(size=nnz).astype(np.float32),
        rng.integers(0, n_cols, nnz).astype(np.int32),
        rows,
        rng.normal(size=n_cols).astype(np.float32),
    )


def us_per_tile(gather: str, rows: int, nt: int, tiles: int, reps: int, seed: int = 0) -> float:
    """Median device time of one call over ``tiles``, per tile, in µs."""
    import jax

    from repro.kernels.common import KernelSchedule
    from repro.kernels.csr import csr_spmv_pallas

    sched = KernelSchedule(rows_per_block=RPB, nnz_tile=nt)
    data, cols, row_ids, x = map(
        jax.device_put, stream(np.random.default_rng(seed), rows * 128, nt, tiles)
    )
    fn = jax.jit(
        lambda d, c, r, x: csr_spmv_pallas(
            d, c, r, x, tiles * RPB, (RPB, nt), sched, gather=gather
        )
    )
    jax.block_until_ready(fn(data, cols, row_ids, x))  # compile
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(data, cols, row_ids, x))
        times.append(time.perf_counter() - t0)
    return statistics.median(times) / tiles * 1e6


def crossing(rows: list[int], vmem: list[float], xla: list[float]) -> float:
    """R at which the fitted in-kernel time per tile meets XLA's mean."""
    b, a = np.polyfit(np.asarray(rows, float), np.asarray(vmem, float), 1)
    return float((statistics.fmean(xla) - a) / b)


def measure(rows: list[int], nnz_tiles: list[int], tiles: int, reps: int) -> dict:
    out = {}
    for nt in nnz_tiles:
        vmem = [us_per_tile("vmem", r, nt, tiles, reps) for r in rows]
        xla = [us_per_tile("xla", r, nt, tiles, reps) for r in rows]
        out[str(nt)] = {
            "rows": rows,
            "vmem_us_per_tile": vmem,
            "xla_us_per_tile": xla,
            "xla_ns_per_entry": [t * 1e3 / nt for t in xla],
            "crossing_rows": crossing(rows, vmem, xla),
        }
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ints = lambda s: [int(v) for v in s.split(",")]  # noqa: E731
    ap.add_argument("--rows", type=ints, default=[64, 128, 192, 512, 1024, 2048])
    ap.add_argument("--nnz-tiles", type=ints, default=[128, 1024])
    ap.add_argument("--tiles", type=int, default=4096)
    ap.add_argument("--reps", type=int, default=10)
    args = ap.parse_args(argv)
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("csr_gather: no TPU found; the timings need the chip", file=sys.stderr)
        sys.exit(2)
    result = measure(args.rows, args.nnz_tiles, args.tiles, args.reps)
    print(json.dumps({"device": dev.device_kind, "tiles": args.tiles, "by_nnz_tile": result}))


if __name__ == "__main__":
    main()
