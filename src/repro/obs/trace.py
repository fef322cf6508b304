"""Structured tracing: nested spans with a thread-safe in-process collector.

Every hot-path section (``session.optimize`` → ``cache.lookup`` →
``kernel.compile`` → ``kernel.execute``) opens a *span*: a named, attributed
interval that records its parent from a per-thread stack, so one served
request becomes a small tree showing exactly where its wall time went —
plan-cache lookup vs. predictor inference vs. Pallas prepare vs. execution.
The paper's headline numbers are *measured* latencies (§6.3); a trace stream
is how a serving reproduction keeps that measurement methodology inspectable
per request instead of trusting aggregate counters.

Every record names its ``root``: the outermost span open on its thread when
it opened (itself, for a root), so all the spans of one request share one
identifier. ``counted_span`` opens a span that also records the calling
thread's resource deltas (``getrusage(RUSAGE_THREAD)``: ``user_s``,
``sys_s``, ``minflt``, ``majflt``, ``nvcsw``, ``nivcsw``) as attributes,
which tells a thread faulting or zeroing pages in the kernel from one that
was descheduled or one that computed.

While a ``jax.profiler`` capture runs, an enabled span also enters a
profiler ``TraceMe`` under its own name, so program spans sit on the
capture's host plane, on the profiler's clock, beside the device's ops.

Cost discipline: an enabled span is one ``perf_counter`` pair, one check
for a running capture and a dict append into a bounded deque (a counted
span adds two ``getrusage`` calls); a disabled tracer hands out a shared
no-op context manager. Export is a JSONL append-log following
``telemetry/recorder.py``'s torn-line convention (a crash mid-append leaves
at most one unparseable trailing line, which ``load_spans`` skips), and
``profile_capture`` wraps a region in ``jax.profiler`` so program spans and
device ops can be opened together in Perfetto.
"""

from __future__ import annotations

import json
import os
import resource
import threading
import time
from collections import deque
from pathlib import Path

from repro.utils.logging import get_logger

log = get_logger("obs.trace")

TRACE_SCHEMA_VERSION = 1


class _NoopSpan:
    """Shared do-nothing context manager: the disabled-tracer fast path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs) -> "_NoopSpan":
        return self


NOOP_SPAN = _NoopSpan()


def _annotation():
    """``jax.profiler.TraceAnnotation``, imported on first use so that the
    tracer itself stays importable without JAX."""
    global _ANNOTATION
    if _ANNOTATION is None:
        from jax.profiler import TraceAnnotation

        _ANNOTATION = TraceAnnotation
    return _ANNOTATION


_ANNOTATION = None


class _Span:
    """One live span; becomes a plain dict in the collector on exit."""

    __slots__ = ("tracer", "name", "attrs", "span_id", "parent_id", "root_id",
                 "t0", "ts", "profiled")

    def __init__(self, tracer: "Tracer", name: str, attrs: dict):
        self.tracer = tracer
        self.name = name
        self.attrs = attrs

    def set(self, **attrs) -> "_Span":
        """Attach attributes discovered mid-span (e.g. hit/miss verdicts)."""
        self.attrs.update(attrs)
        return self

    def __enter__(self) -> "_Span":
        tr = self.tracer
        self.span_id = tr._next_id()
        stack = tr._stack()
        self.parent_id = stack[-1] if stack else None
        self.root_id = stack[0] if stack else self.span_id
        stack.append(self.span_id)
        annotation = _annotation()
        self.profiled = None
        if annotation.is_enabled():  # a profiler capture is running
            self.profiled = annotation(self.name)
            self.profiled.__enter__()
        self.ts = time.time()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        dur = time.perf_counter() - self.t0
        if self.profiled is not None:
            self.profiled.__exit__(exc_type, exc, tb)
        stack = self.tracer._stack()
        if stack and stack[-1] == self.span_id:
            stack.pop()
        rec = {
            "name": self.name,
            "id": self.span_id,
            "parent": self.parent_id,
            "root": self.root_id,
            "ts": self.ts,
            "dur_s": dur,
            "thread": threading.get_ident(),
        }
        if exc_type is not None:
            rec["error"] = exc_type.__name__
        if self.attrs:
            rec["attrs"] = self.attrs
        self.tracer._collect(rec)
        return False


_RUSAGE_THREAD = getattr(resource, "RUSAGE_THREAD", None)  # Linux only


class _CountedSpan(_Span):
    """A span that also records its thread's resource deltas as attributes."""

    __slots__ = ("usage",)

    def __enter__(self) -> "_CountedSpan":
        super().__enter__()
        self.usage = resource.getrusage(_RUSAGE_THREAD)
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        u0, u1 = self.usage, resource.getrusage(_RUSAGE_THREAD)
        self.attrs.update(
            user_s=u1.ru_utime - u0.ru_utime,
            sys_s=u1.ru_stime - u0.ru_stime,
            minflt=u1.ru_minflt - u0.ru_minflt,
            majflt=u1.ru_majflt - u0.ru_majflt,
            nvcsw=u1.ru_nvcsw - u0.ru_nvcsw,
            nivcsw=u1.ru_nivcsw - u0.ru_nivcsw,
        )
        return super().__exit__(exc_type, exc, tb)


class Tracer:
    """Thread-safe span collector with bounded memory and JSONL export.

    ``max_spans`` bounds the in-process buffer (oldest spans drop first —
    a serving loop must not grow RSS with its request count); ``drops``
    counts what the bound discarded so exports are honest about truncation.
    """

    def __init__(self, *, enabled: bool = True, max_spans: int = 65536):
        self.enabled = enabled
        self.max_spans = int(max_spans)
        self._spans: deque[dict] = deque(maxlen=self.max_spans)
        self._exported = 0  # spans already flushed to the JSONL log
        self.drops = 0
        self._lock = threading.Lock()
        self._local = threading.local()
        self._id_counter = 0

    # -------------------------------------------------------------- internals
    def _stack(self) -> list[int]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _next_id(self) -> int:
        with self._lock:
            self._id_counter += 1
            return self._id_counter

    def _collect(self, rec: dict) -> None:
        with self._lock:
            if len(self._spans) == self.max_spans:
                self.drops += 1
                if self._exported:  # the dropped span was the oldest
                    self._exported -= 1
            self._spans.append(rec)

    # -------------------------------------------------------------------- api
    def span(self, name: str, **attrs):
        """Open a nested span; use as ``with tracer.span("cache.lookup"):``."""
        if not self.enabled:
            return NOOP_SPAN
        return _Span(self, name, attrs)

    def counted_span(self, name: str, **attrs):
        """``span`` that also records the calling thread's resource deltas
        (CPU time in user and kernel mode, page faults, context switches).
        Where the platform has no per-thread usage it is a plain span."""
        if not self.enabled:
            return NOOP_SPAN
        if _RUSAGE_THREAD is None:
            return _Span(self, name, attrs)
        return _CountedSpan(self, name, attrs)

    def spans(self) -> list[dict]:
        with self._lock:
            return list(self._spans)

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()
            self._exported = 0
            self.drops = 0

    def summary(self) -> dict:
        """Per-name counts + total duration of the buffered spans."""
        by_name: dict[str, dict] = {}
        for rec in self.spans():
            cell = by_name.setdefault(rec["name"], {"count": 0, "total_s": 0.0})
            cell["count"] += 1
            cell["total_s"] += rec["dur_s"]
        return {"spans": sum(c["count"] for c in by_name.values()),
                "drops": self.drops, "by_name": by_name}

    # ------------------------------------------------------------ persistence
    def export_jsonl(self, path: str | Path) -> int:
        """Append spans not yet exported to a JSONL shard; returns lines.

        Same crash tolerance as the telemetry recorder: if the file's last
        byte is not a newline (a torn previous append), a newline is
        prepended so only that one already-torn line is lost on replay."""
        path = Path(path)
        with self._lock:
            fresh = list(self._spans)[self._exported:]
            self._exported = len(self._spans)
        if not fresh:
            return 0
        path.parent.mkdir(parents=True, exist_ok=True)
        chunk = "".join(json.dumps(r, sort_keys=True) + "\n" for r in fresh)
        if path.exists() and path.stat().st_size:
            with open(path, "rb") as f:
                f.seek(-1, os.SEEK_END)
                if f.read(1) != b"\n":
                    chunk = "\n" + chunk
        with open(path, "a") as f:
            f.write(chunk)
            f.flush()
            os.fsync(f.fileno())
        return len(fresh)


def load_spans(path: str | Path) -> list[dict]:
    """Replay a span JSONL shard, skipping torn/foreign lines."""
    out = []
    for line in Path(path).read_text().splitlines():
        line = line.strip()
        if not line:
            continue
        try:
            rec = json.loads(line)
        except ValueError:
            continue  # torn trailing line from an interrupted append
        if isinstance(rec, dict) and "name" in rec and "dur_s" in rec:
            out.append(rec)
    return out


def span_children(spans: list[dict], parent_id) -> list[dict]:
    """The direct children of one span (trace-tree navigation helper)."""
    return [s for s in spans if s.get("parent") == parent_id]


_TRACER = Tracer()


def get_tracer() -> Tracer:
    """The process-wide tracer every instrumented module shares."""
    return _TRACER


def span(name: str, **attrs):
    """Module-level convenience: ``with span("session.optimize"): ...``."""
    return _TRACER.span(name, **attrs)


def counted_span(name: str, **attrs):
    """Module-level ``Tracer.counted_span`` on the process-wide tracer."""
    return _TRACER.counted_span(name, **attrs)


class profile_capture:
    """Wrap a region in ``jax.profiler`` (Perfetto/TensorBoard).

    ``with profile_capture("artifacts/profile"):`` captures every XLA/Pallas
    launch inside, and every enabled span opened inside, into a trace a
    real viewer can open. A profile that was asked for and cannot be taken
    (no profiler support, a capture already running) raises: a run that
    claims to be profiled must be."""

    def __init__(self, log_dir: str | Path):
        self.log_dir = str(log_dir)

    def __enter__(self):
        import jax

        jax.profiler.start_trace(self.log_dir)
        log.info("jax profiler capture -> %s", self.log_dir)
        return self

    def __exit__(self, *exc) -> bool:
        import jax

        jax.profiler.stop_trace()
        return False
