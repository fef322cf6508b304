"""Peaks of each device kind, and the bytes an SpMV cannot avoid moving."""

from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    """The peaks table's row for ``device_kind``; an unknown kind raises."""
    table = json.loads(Path(path).read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path.name}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def compulsory_bytes(nnz: int, n_rows: int, n_cols: int, value_bytes: int = 4) -> int:
    """Bytes any SpMV ``y = A x`` must move at this value width: every
    stored value once, x once, y once. No format moves less, whatever
    indices, padding or gathered planes an implementation adds on top."""
    return value_bytes * (int(nnz) + int(n_cols) + int(n_rows))


def least_seconds(nnz: int, n_rows: int, n_cols: int, peak: dict) -> float:
    """Shortest time one SpMV can take: the longer of its compulsory bytes
    at peak HBM bandwidth and its 2 nnz operations at peak FLOP/s. The
    bytes bound it at every matrix the benchmark runs."""
    return max(compulsory_bytes(nnz, n_rows, n_cols) / peak["hbm_bytes_per_s"],
               2 * int(nnz) / peak["bf16_flops_per_s"])
