"""Reduction of a profiler trace to the numbers the per-layer readers use.

The benchmark wraps its measured window in a ``chipbench.window`` and each
request in a ``chipbench.request`` annotation (``jax.profiler.TraceAnnotation``),
so they sit on the trace's own clock beside the device's events. From the
trace this module takes:

* device busy time: the union of the ``XLA Ops`` events of each device
  plane inside the window, averaged over the devices;
* SpMV programs: ``XLA Modules`` events whose name holds ``SPMV_PROGRAM``;
  the ops inside them are split into Mosaic kernels (an op whose HLO text,
  the event's name, calls ``KERNEL_MARK``) and the rest, which XLA ran for
  the program (today the gather of x);
* idle gaps: the stretches of the window in which no op ran, each labelled
  with the innermost program span (``obs.trace``) or benchmark annotation
  open at its middle;
* the time of every op and of every XLA module in the window, by name, so
  that a reader can take a kernel's time inside any program, or a
  collective's, without a change here.

All times are nanoseconds from the start of the profile.
"""

from __future__ import annotations

import glob
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path

WINDOW = "chipbench.window"
REQUEST = "chipbench.request"
SPMV_PROGRAM = "_jitted_spmv"
KERNEL_MARK = 'custom_call_target="tpu_custom_call"'
DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclass(frozen=True)
class Event:
    name: str  # on a device's ops line, the op's whole HLO text
    start: float
    end: float

    @property
    def short(self) -> str:
        """The op's name alone: ``%fusion.1 = f32[...] ...`` -> ``fusion.1``."""
        return self.name.split(" = ", 1)[0].lstrip("%")


@dataclass
class Trace:
    start_epoch_ns: int
    devices: dict[str, dict[str, list[Event]]] = field(default_factory=dict)
    host: list[Event] = field(default_factory=list)


def _event(ev) -> Event:
    return Event(ev.name, float(ev.start_ns), float(ev.end_ns))


def from_profile(profile) -> Trace:
    """A ``jax.profiler.ProfileData`` as plain events."""
    start = 0
    env = profile.find_plane_with_name("Task Environment")
    if env is not None:
        start = int(dict(env.stats).get("profile_start_time", 0))
    trace = Trace(start)
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PREFIX) and not plane.name.startswith("/device:CUSTOM"):
            trace.devices[plane.name] = {
                line.name: [_event(e) for e in line.events] for line in plane.lines
            }
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                trace.host.extend(_event(e) for e in line.events)
    return trace


def load(log_dir: str | Path) -> Trace:
    """The newest ``.xplane.pb`` under a ``jax.profiler`` log directory."""
    import jax

    paths = sorted(glob.glob(str(Path(log_dir) / "**" / "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return from_profile(jax.profiler.ProfileData.from_file(paths[-1]))


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged ``intervals`` clipped to ``[lo, hi]``."""
    out: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(merged, lo: float, hi: float) -> float:
    """Length of ``[lo, hi]`` that merged intervals cover."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged)


@dataclass
class Reduction:
    """What one traced window held; the per-layer readers divide from here."""

    window_ns: float
    busy_ns: float  # averaged over the devices
    spmv_calls: int  # SpMV programs that ran in the window (all devices)
    kernel_ns: float  # Mosaic kernel time inside them
    xla_ns: float  # every other op's time inside them
    request_ns: float  # time inside the benchmark's request annotations
    request_idle_ns: float  # of which no device op ran
    device_ops: list[tuple[str, float]]  # (op, seconds), longest first
    idle_gaps: list[tuple[str, float]]  # (what the host did, seconds)
    op_ns: dict[str, float]  # every op's time in the window, by short name, over all devices
    module_ns: dict[str, float]  # every XLA module's time in the window, by name, over all devices
    devices: int  # device planes reduced


def _label(mid: float, spans: list[tuple[float, float, str]]) -> str:
    open_ = [(e - s, name) for s, e, name in spans if s <= mid <= e]
    return min(open_)[1] if open_ else "outside any span"


def reduce(trace: Trace, program_spans=(), top: int = 10) -> Reduction:
    """Reduce the window of one traced run.

    ``program_spans`` are the program's own ``obs.trace`` records (``ts`` in
    seconds since the epoch, ``dur_s``); they are placed on the trace's
    clock to name what the host did in each idle gap.
    """
    windows = [e for e in trace.host if e.name == WINDOW]
    if not windows:
        raise ValueError(f"trace has no {WINDOW!r} annotation")
    lo, hi = windows[0].start, windows[0].end
    busy_by_dev, kernel_ns, xla_ns, calls = [], 0.0, 0.0, 0
    op_time: dict[str, float] = defaultdict(float)
    module_time: dict[str, float] = defaultdict(float)
    all_busy = []
    for lines in trace.devices.values():
        ops = [e for e in lines.get(OPS_LINE, []) if e.end > lo and e.start < hi]
        merged = union([(e.start, e.end) for e in ops], lo, hi)
        busy_by_dev.append(sum(e - s for s, e in merged))
        all_busy.extend(merged)
        for e in ops:
            op_time[e.short] += min(e.end, hi) - max(e.start, lo)
        for m in lines.get(MODULES_LINE, []):
            if m.end > lo and m.start < hi:
                module_time[m.name] += min(m.end, hi) - max(m.start, lo)
        programs = [m for m in lines.get(MODULES_LINE, [])
                    if SPMV_PROGRAM in m.name and lo <= m.start < hi]
        calls += len(programs)
        spans = union([(m.start, m.end) for m in programs], lo, hi)
        for e in ops:
            inside = covered(spans, e.start, e.end)
            if inside <= 0:
                continue
            if KERNEL_MARK in e.name:
                kernel_ns += inside
            else:
                xla_ns += inside
    n_dev = len(busy_by_dev)
    if n_dev == 0:
        raise ValueError("trace has no device plane")
    busy_any = union(all_busy, lo, hi)
    requests = [(e.start, e.end) for e in trace.host if e.name == REQUEST and e.start >= lo]
    request_ns = sum(e - s for s, e in requests)
    request_idle = sum((e - s) - covered(busy_any, s, e) for s, e in requests)

    labelled = [(e.start, e.end, e.name) for e in trace.host if e.name in (REQUEST, WINDOW)]
    for sp in program_spans:
        s = sp["ts"] * 1e9 - trace.start_epoch_ns
        labelled.append((s, s + sp["dur_s"] * 1e9, sp["name"]))
    gaps, prev = [], lo
    for s, e in busy_any + [(hi, hi)]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    gaps.sort(key=lambda g: g[0] - g[1])
    idle = [(_label((s + e) / 2, labelled), (e - s) / 1e9) for s, e in gaps[:top]]
    ops_top = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    return Reduction(
        window_ns=hi - lo,
        busy_ns=sum(busy_by_dev) / n_dev,
        spmv_calls=calls,
        kernel_ns=kernel_ns,
        xla_ns=xla_ns,
        request_ns=request_ns,
        request_idle_ns=request_idle,
        device_ops=[(name, ns / 1e9) for name, ns in ops_top],
        idle_gaps=idle,
        op_ns=dict(op_time),
        module_ns=dict(module_time),
        devices=n_dev,
    )
