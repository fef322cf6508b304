"""Chip benchmark of the Auto-SpMV serving and solver path.

``python -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` on the chip and prints one JSON result
line. Everything the benchmark measures with (matrix generator, reference,
trace reduction, peaks, byte counts) lives in this package, apart from the
program it drives.
"""
