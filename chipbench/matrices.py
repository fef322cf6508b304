"""The benchmark's own copy of the Table-7 matrix generator.

Copied from the program's ``sparse/generate.py`` (the ``fem`` and
``denserows`` pattern classes and ``_row_major_expand``) so that a later
change there cannot move the yardstick. Each class is kept as its draw of
coordinates; ``program_copy`` scatters them as the program does, byte for
byte.

What a cell runs, ``generate``, holds the published matrix where the
program's generator does not:

* the class's coordinates are drawn until exactly the published count of
  distinct entries is reached (the program lets duplicates collapse and
  falls 3-14% short); the surplus of the last draw is dropped at random;
* a ``symmetric`` matrix's published count is its stored lower triangle
  with the diagonal, so the draw fills the strict lower triangle, the whole
  diagonal is set and the triangle mirrored: it runs 2·nnz - n entries;
* the values come from a second generator seeded from ``--seed``; the
  pattern is the one the configuration's positional seed fixes, the same
  for every seed;
* every page of the dense array is written, on 4 KiB pages, so that its
  layout in host memory is the same on every machine.
"""

from __future__ import annotations

import mmap

import numpy as np

MAX_DRAWS = 64


def _row_major_expand(counts: np.ndarray) -> np.ndarray:
    return np.repeat(np.arange(counts.size), counts)


def _fem_coords(n: int, avg: float, rng) -> tuple[np.ndarray, np.ndarray]:
    # near-constant row counts, mostly banded with a few far couplings
    counts = np.clip(rng.normal(avg, max(avg * 0.05, 1.0), size=n).astype(np.int64), 1, None)
    rows = _row_major_expand(counts)
    band = max(int(avg * 3), 8)
    local = rng.integers(-band // 2, band // 2 + 1, size=rows.size)
    cols = np.clip(rows + local, 0, n - 1)
    far = rng.random(rows.size) < 0.05
    cols[far] = rng.integers(0, n, size=int(far.sum()))
    return rows, cols


def _denserows_coords(n: int, avg: float, rng) -> tuple[np.ndarray, np.ndarray]:
    counts = np.clip(rng.normal(avg, avg * 0.3, size=n).astype(np.int64), 1, n - 1)
    rows = _row_major_expand(counts)
    cols = rng.integers(0, n, size=rows.size)
    return rows, cols


PATTERNS = {"fem": _fem_coords, "denserows": _denserows_coords}


def _size(matrix: dict, scale: float) -> tuple[int, float]:
    """n and the mean row count to draw at, as the program scales them."""
    n = max(int(matrix["n"] * scale), 64)
    return n, min(matrix["nnz"] / matrix["n"], n / 2)


def program_copy(matrix: dict, scale: float = 1.0) -> np.ndarray:
    """The program's generator as copied: its output byte for byte."""
    n, avg = _size(matrix, scale)
    rng = np.random.default_rng(matrix["seed"])
    rows, cols = PATTERNS[matrix["pattern"]](n, avg, rng)
    dense = np.zeros((n, n), dtype=np.float32)
    dense[rows, cols] = rng.uniform(0.1, 1.0, size=rows.size).astype(np.float32)
    return dense


def stored(matrix: dict, scale: float = 1.0) -> int:
    """Entries the configuration's count stands for at ``scale``: the
    published count at 1.0, held to half the matrix (of its lower triangle,
    where symmetric) at the small sizes tests run."""
    n, _ = _size(matrix, scale)
    half = n * (n + 1) // 4 if matrix.get("symmetric") else n * n // 2
    return min(round(matrix["nnz"] * n / matrix["n"]), half)


def entries(matrix: dict, scale: float = 1.0) -> int:
    """Nonzeros of the matrix ``generate`` makes."""
    n, _ = _size(matrix, scale)
    k = stored(matrix, scale)
    return 2 * k - n if matrix.get("symmetric") else k


def _distinct(draw, target: int, space: int, rng) -> np.ndarray:
    """Sorted keys of the first ``target`` distinct keys ``draw`` yields."""
    stamp = np.zeros(space, dtype=np.uint8)  # draw that first set each key
    for b in range(1, MAX_DRAWS + 1):
        keys = draw()
        keys = keys[stamp[keys] == 0]
        stamp[keys] = b
        count = int(np.count_nonzero(stamp))
        if count >= target:
            fresh = np.flatnonzero(stamp == b)
            stamp[rng.choice(fresh, count - target, replace=False)] = 0
            return np.flatnonzero(stamp)
    raise ValueError(f"{target} distinct entries not reached in {MAX_DRAWS} draws")


def host_array(n: int) -> np.ndarray:
    """An n x n float32 zero array with every page written, on 4 KiB pages."""
    buf = mmap.mmap(-1, n * n * 4, flags=mmap.MAP_PRIVATE | mmap.MAP_ANONYMOUS)
    buf.madvise(mmap.MADV_NOHUGEPAGE)
    dense = np.frombuffer(buf, dtype=np.float32).reshape(n, n)
    dense.fill(0.0)
    return dense


def seed_words(seed: int) -> int:
    """A ``--seed`` as a non-negative word for ``SeedSequence``."""
    return int(seed) % 2**64


def generate(matrix: dict, value_seed: int, scale: float = 1.0) -> np.ndarray:
    """Dense float32 matrix of one configuration's ``matrix`` entry.

    ``matrix`` holds ``pattern``, ``n`` and ``nnz`` (as published), the
    positional ``seed`` and, where the published matrix is, ``symmetric``.
    ``scale`` shrinks n as the program's generator does (tests only; cells
    run at 1.0).
    """
    n, avg = _size(matrix, scale)
    coords = PATTERNS[matrix["pattern"]]
    rng = np.random.default_rng(matrix["seed"])
    values = np.random.default_rng([seed_words(value_seed), 0])
    symmetric = bool(matrix.get("symmetric"))

    def draw() -> np.ndarray:
        rows, cols = coords(n, avg, rng)
        if not symmetric:
            return rows * n + cols
        hi, lo = np.maximum(rows, cols), np.minimum(rows, cols)
        keep = hi != lo
        return hi[keep] * n + lo[keep]

    target = stored(matrix, scale) - (n if symmetric else 0)
    rows, cols = np.divmod(_distinct(draw, target, n * n, rng), n)
    dense = host_array(n)
    if symmetric:
        diag = np.arange(n)
        dense[diag, diag] = values.uniform(0.1, 1.0, size=n).astype(np.float32)
    vals = values.uniform(0.1, 1.0, size=rows.size).astype(np.float32)
    dense[rows, cols] = vals
    if symmetric:
        dense[cols, rows] = vals
    return dense


def inputs(config: dict, seed: int, scale: float = 1.0) -> np.ndarray:
    """A driver's ``inputs``: the dense matrix of a configuration's ``matrix``."""
    return generate(config["matrix"], seed, scale)


def start_vector(n: int, seed: int, solve_index: int, stream: int = 1) -> np.ndarray:
    """Dense float64 start vector of one solve, drawn from (seed, index);
    ``stream`` keeps the warm-up's vectors apart from the window's."""
    return np.random.default_rng([seed_words(seed), stream, solve_index]).standard_normal(n)
