"""The program's own spans of the traced window, for the readers that sum them.

The benchmark clears the program's tracer (``repro.obs.trace``) when the
window starts and opens no program span between the window's end and the
readers, so the tracer holds the spans of the requests answered in the
window. A reader returns nothing where the tracer dropped spans (a sum would
come out short) or holds none of the kind it reads (a program without that
span or counter).
"""

from __future__ import annotations


def window() -> tuple[list[dict], int]:
    """The spans the program's tracer holds, and how many it dropped."""
    from repro.obs.trace import get_tracer

    tracer = get_tracer()
    return tracer.spans(), tracer.drops


def named(spans: list[dict], name: str) -> list[dict]:
    return [s for s in spans if s["name"] == name]


def ms_per_spmv(spans: list[dict], drops: int, spmvs: int, name: str) -> float | None:
    """Sum of the durations of the spans called ``name`` per SpMV answered,
    in milliseconds."""
    found = named(spans, name)
    if drops or not found or spmvs == 0:
        return None
    return sum(s["dur_s"] for s in found) * 1e3 / spmvs
