"""Observability subsystem: span nesting, disabled-mode no-ops, JSONL
round-trips with torn-line recovery, Prometheus text validity, energy
accounting against the cost model, fleet shard aggregation, and the
``/metrics`` scrape surface end to end through ``SpmvServer``."""

import json
import math
import re
import threading
import urllib.request

import numpy as np
import pytest

from repro.core.session import AutoSpmvSession
from repro.kernels.common import DEFAULT_SCHEDULE
from repro.kernels.ops import clear_kernel_memo
from repro.obs import set_obs_enabled
from repro.obs.aggregate import merge_shards
from repro.obs.energy import EnergyAccountant
from repro.obs.http import ObsHTTPServer
from repro.obs.metrics import MetricsRegistry, get_metrics, reset_metrics
from repro.obs.trace import (
    NOOP_SPAN,
    Tracer,
    counted_span,
    get_tracer,
    load_spans,
    profile_capture,
    span,
    span_children,
)
from repro.sparse.generate import random_matrix
from repro.sparse.registry import MatrixStats
from repro.train.serve import SpmvRequest, SpmvServer

from tests.test_partition import hetero_matrix
from tests.test_telemetry import _fake_tuner, _mat


@pytest.fixture(autouse=True)
def _clean_obs():
    """Process-global tracer/registry: isolate every test, leave obs on."""
    set_obs_enabled(True)
    get_tracer().clear()
    reset_metrics()
    yield
    set_obs_enabled(True)
    get_tracer().clear()
    reset_metrics()


# ------------------------------------------------------------------- tracing


def test_span_nesting_and_ordering():
    tracer = Tracer()
    with tracer.span("session.optimize", objective="latency") as outer:
        with tracer.span("cache.lookup"):
            pass
        with tracer.span("kernel.compile", fmt="csr"):
            pass
        outer.set(cache_hit=False)
    spans = tracer.spans()
    # children close before the parent, so the parent is recorded last
    assert [s["name"] for s in spans] == [
        "cache.lookup", "kernel.compile", "session.optimize",
    ]
    root = spans[-1]
    assert root["parent"] is None
    assert root["attrs"] == {"objective": "latency", "cache_hit": False}
    kids = span_children(spans, root["id"])
    assert {s["name"] for s in kids} == {"cache.lookup", "kernel.compile"}
    assert all(s["dur_s"] >= 0 for s in spans)
    # sibling ordering: cache.lookup entered (and exited) first
    assert kids[0]["ts"] <= kids[1]["ts"]


def test_span_records_error_and_unwinds_stack():
    tracer = Tracer()
    with pytest.raises(ValueError):
        with tracer.span("outer"):
            with tracer.span("inner"):
                raise ValueError("boom")
    spans = tracer.spans()
    assert [s["name"] for s in spans] == ["inner", "outer"]
    assert spans[0]["error"] == "ValueError"
    # the stack fully unwound: a new span is a root again
    with tracer.span("fresh"):
        pass
    assert tracer.spans()[-1]["parent"] is None


def test_spans_are_per_thread_trees():
    tracer = Tracer()

    def worker():
        with tracer.span("thread.root"):
            with tracer.span("thread.child"):
                pass

    with tracer.span("main.root"):
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    spans = {s["name"]: s for s in tracer.spans()}
    # the worker's root must not become a child of the main thread's span
    assert spans["thread.root"]["parent"] is None
    assert spans["thread.child"]["parent"] == spans["thread.root"]["id"]


def test_disabled_tracer_and_registry_are_noops():
    tracer = Tracer(enabled=False)
    s = tracer.span("anything", attr=1)
    assert s is NOOP_SPAN  # the shared singleton: zero allocation per span
    with s as ctx:
        ctx.set(more=2)
    assert tracer.spans() == []

    reg = MetricsRegistry(enabled=False)
    c = reg.counter("spmv_cache_hits_total")
    c.inc()
    c.inc(5)
    assert c.value == 0.0
    h = reg.histogram("spmv_request_latency_seconds")
    h.observe(0.5)
    assert h.count == 0
    g = reg.gauge("g")
    g.set(3.0)
    assert math.isnan(g.value)


def test_disabled_tracer_hands_out_the_shared_noop_for_counted_spans_too():
    import tracemalloc

    tracer = Tracer(enabled=False)
    assert tracer.counted_span("solver.iterate", iteration=1) is NOOP_SPAN
    tracemalloc.start()
    try:
        before = tracemalloc.get_traced_memory()[0]
        for _ in range(10_000):
            with tracer.span("kernel.execute"):
                pass
            with tracer.counted_span("solver.iterate"):
                pass
        held = tracemalloc.get_traced_memory()[0] - before
    finally:
        tracemalloc.stop()
    assert held < 1024  # nothing kept per span
    assert tracer.spans() == []


def test_every_span_names_its_root():
    tracer = Tracer()
    with tracer.span("solver.solve"):
        with tracer.span("solver.setup"):
            with tracer.span("session.serve"):
                pass
        with tracer.span("solver.iterate"):
            pass
    with tracer.span("other.request"):
        pass
    spans = {s["name"]: s for s in tracer.spans()}
    root = spans["solver.solve"]["id"]
    assert {spans[n]["root"] for n in ("solver.solve", "solver.setup", "session.serve",
                                       "solver.iterate")} == {root}
    assert spans["other.request"]["root"] == spans["other.request"]["id"] != root


def test_counted_span_records_the_threads_usage(monkeypatch):
    import resource
    from types import SimpleNamespace

    import repro.obs.trace as trace_mod

    def usage(t, faults, switches):
        return SimpleNamespace(ru_utime=t, ru_stime=t / 4, ru_minflt=faults, ru_majflt=1,
                               ru_nvcsw=switches, ru_nivcsw=2 * switches)

    readings = iter([usage(1.0, 100, 3), usage(1.5, 160, 5)])
    who = []

    def fake_getrusage(which):
        who.append(which)
        return next(readings)

    monkeypatch.setattr(trace_mod.resource, "getrusage", fake_getrusage)
    tracer = Tracer()
    with tracer.counted_span("solver.setup", solver="power") as sp:
        sp.set(done=True)
        with tracer.span("session.serve"):
            pass
    inner, outer = tracer.spans()
    assert who == [resource.RUSAGE_THREAD] * 2  # the calling thread's own usage
    assert outer["attrs"] == pytest.approx({
        "solver": "power", "done": True, "user_s": 0.5, "sys_s": 0.125, "minflt": 60,
        "majflt": 0, "nvcsw": 2, "nivcsw": 4,
    })
    assert "attrs" not in inner  # a plain span pays no syscalls


def test_counted_span_reads_real_usage():
    tracer = Tracer()
    with tracer.counted_span("session.fingerprint"):
        np.ones(1 << 20, np.float64).sum()
    (rec,) = tracer.spans()
    for key in ("user_s", "sys_s", "minflt", "majflt", "nvcsw", "nivcsw"):
        assert rec["attrs"][key] >= 0, key


def test_optimize_many_fingerprints_each_matrix_under_a_span():
    clear_kernel_memo()
    session = AutoSpmvSession(_fake_tuner())
    a = random_matrix(96, 5.0, "fem", seed=1).astype(np.float32)
    b = random_matrix(96, 5.0, "fem", seed=2).astype(np.float32)
    get_tracer().clear()
    session.optimize_many([a, b, a], "latency")
    spans = get_tracer().spans()
    fps = [s for s in spans if s["name"] == "session.fingerprint"]
    assert len(fps) == 3  # one per matrix hashed; the optimize calls reuse them
    assert all(s["parent"] is None and s["attrs"]["bytes"] == a.nbytes for s in fps)
    assert all("sys_s" in s["attrs"] for s in fps)
    # serve_optimize hashes inside its own span
    get_tracer().clear()
    session.serve_optimize(b, "latency")
    spans = {s["name"]: s for s in get_tracer().spans()}
    assert spans["session.fingerprint"]["parent"] == spans["session.serve"]["id"]
    assert spans["session.optimize"]["parent"] == spans["session.serve"]["id"]


def test_spans_reach_the_profilers_host_plane(tmp_path):
    """While a capture runs, every span is also a profiler event under its
    own name, on the host plane the benchmark's trace reduction reads, and
    placed where its ``ts`` puts it."""
    from chipbench import tracing

    tracer = get_tracer()
    with span("before.capture"):
        pass
    with profile_capture(tmp_path):
        for i in range(3):
            with span("probe.request", i=i):
                with counted_span("probe.step"):
                    np.ones(1000).sum()
    trace = tracing.load(tmp_path)
    records = [r for r in tracer.spans() if r["name"].startswith("probe.")]
    events = [e for e in trace.host if e.name.startswith("probe.")]
    assert len(records) == len(events) == 6
    assert not [e for e in trace.host if e.name == "before.capture"]
    for name in ("probe.request", "probe.step"):
        mine = sorted((r for r in records if r["name"] == name), key=lambda r: r["ts"])
        theirs = sorted((e for e in events if e.name == name), key=lambda e: e.start)
        for r, e in zip(mine, theirs):
            placed = r["ts"] * 1e9 - trace.start_epoch_ns
            assert abs(e.start - placed) < 5e6  # ns: the same clock
            assert e.end - e.start <= r["dur_s"] * 1e9 + 5e6


def test_profile_capture_raises_when_a_profile_cannot_be_taken(tmp_path):
    """A run that asked for a profile must not carry on without one."""
    with profile_capture(tmp_path / "outer"):
        with pytest.raises(RuntimeError):
            with profile_capture(tmp_path / "inner"):
                pass


def test_trace_jsonl_roundtrip_with_torn_line(tmp_path):
    tracer = Tracer()
    with tracer.span("a"):
        with tracer.span("b"):
            pass
    path = tmp_path / "trace.jsonl"
    assert tracer.export_jsonl(path) == 2
    assert tracer.export_jsonl(path) == 0  # nothing fresh: no duplicate lines

    # crash simulation: a torn, newline-less partial record at the tail
    with open(path, "a") as f:
        f.write('{"name": "torn", "dur')
    with tracer.span("c"):
        pass
    assert tracer.export_jsonl(path) == 1

    spans = load_spans(path)
    assert [s["name"] for s in spans] == ["b", "a", "c"]
    assert spans[0]["parent"] == spans[1]["id"]


def test_tracer_bounded_buffer_counts_drops():
    tracer = Tracer(max_spans=4)
    for i in range(10):
        with tracer.span(f"s{i}"):
            pass
    assert len(tracer.spans()) == 4
    assert tracer.drops == 6
    assert tracer.summary()["drops"] == 6


# ------------------------------------------------------------------- metrics

_PROM_LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? (NaN|[-+0-9.eE]+)$"
)


def test_prometheus_text_validity():
    reg = MetricsRegistry()
    reg.counter("spmv_cache_hits_total").inc(3)
    reg.gauge("spmv_avg_power_watts", fmt="csr", objective="latency").set(1.5)
    h = reg.histogram("spmv_request_latency_seconds", objective="latency")
    for v in np.linspace(0.001, 0.1, 100):
        h.observe(float(v))
    text = reg.to_prometheus()
    lines = text.strip().splitlines()
    for line in lines:
        if line.startswith("# TYPE "):
            assert line.split()[-1] in ("counter", "gauge", "summary")
            continue
        assert _PROM_LINE.match(line), f"invalid exposition line: {line!r}"
    assert "# TYPE spmv_cache_hits_total counter" in text
    assert "spmv_cache_hits_total 3" in text
    assert 'spmv_avg_power_watts{fmt="csr",objective="latency"} 1.5' in text
    for q in ("0.5", "0.9", "0.99"):
        assert f'quantile="{q}"' in text
    assert "spmv_request_latency_seconds_count{objective=\"latency\"} 100" in text


def test_registry_reset_keeps_instrument_handles():
    reg = MetricsRegistry()
    c = reg.counter("spmv_cache_hits_total")
    c.inc(7)
    reg.reset()
    assert c.value == 0.0  # zeroed IN PLACE: cached handles stay live
    c.inc()
    assert reg.counter("spmv_cache_hits_total") is c
    assert reg.snapshot()["counters"]["spmv_cache_hits_total"] == 1.0


def test_labelled_instruments_are_distinct():
    reg = MetricsRegistry()
    reg.counter("spmv_requests_total", fmt="csr", objective="latency").inc()
    reg.counter("spmv_requests_total", fmt="ell", objective="latency").inc(2)
    snap = reg.snapshot()["counters"]
    assert snap['spmv_requests_total{fmt="csr",objective="latency"}'] == 1.0
    assert snap['spmv_requests_total{fmt="ell",objective="latency"}'] == 2.0


# -------------------------------------------------------------------- energy


def test_energy_accounting_against_cost_model():
    from repro.core.objectives import TpuCostModel

    dense = random_matrix(256, 8.0, "fem", seed=3).astype(np.float32)
    modeled = TpuCostModel().evaluate(MatrixStats(dense), "csr", DEFAULT_SCHEDULE)
    assert modeled.feasible and modeled.energy > 0

    reg = MetricsRegistry()
    acc = EnergyAccountant(reg)
    measured = 2.0 * modeled.latency  # kernel ran slower than modeled
    for _ in range(3):
        acc.observe(
            fmt="csr", objective="latency",
            measured_s=measured, modeled=modeled.as_dict(),
        )
    cell = acc.cell("csr", "latency")
    assert cell.requests == 3
    assert cell.energy_j == pytest.approx(3 * modeled.energy)
    # energy stays modeled; average power re-derives from MEASURED time, so
    # a 2x-slower kernel shows half the modeled average power
    assert cell.avg_power_w == pytest.approx(modeled.power / 2.0, rel=1e-6)
    # efficiency = useful FLOP rate per watt; the useful-work numerator is
    # inverted from the modeled triple (eff * P * t * 1e6)
    useful = modeled.efficiency * modeled.power * modeled.latency * 1e6
    expect_eff = (3 * useful) / cell.latency_s / 1e6 / cell.avg_power_w
    assert cell.efficiency_mflops_per_w == pytest.approx(expect_eff, rel=1e-6)

    # aggregates mirrored into gauges for the /metrics scrape
    g = reg.gauge("spmv_energy_joules_total", fmt="csr", objective="latency")
    assert g.value == pytest.approx(cell.energy_j)
    summary = acc.summary()
    assert summary["per_format"]["csr"]["requests"] == 3


def test_energy_accounting_degrades_without_model():
    acc = EnergyAccountant(MetricsRegistry())
    cell = acc.observe(fmt="ell", objective="energy", measured_s=0.01, modeled=None)
    assert cell.requests == 1
    assert cell.energy_j == 0.0
    assert cell.avg_power_w == 0.0
    assert cell.efficiency_mflops_per_w == 0.0


# ----------------------------------------------------------------- aggregate


def test_aggregate_merges_multi_instance_shards(tmp_path):
    shards = []
    rngs = np.random.default_rng(0)
    for instance in ("inst-a", "inst-b"):
        reg = MetricsRegistry()
        reg.counter("spmv_cache_hits_total").inc(4)
        reg.gauge("spmv_avg_power_watts", fmt="csr").set(
            2.0 if instance == "inst-a" else 4.0
        )
        h = reg.histogram("spmv_request_latency_seconds")
        for v in rngs.uniform(0.001, 0.1, size=100):
            h.observe(float(v))
        path = tmp_path / f"metrics-{instance}.jsonl"
        reg.write_shard(path, instance)
        shards.append(path)

    tracer = Tracer()
    with tracer.span("session.optimize"):
        with tracer.span("kernel.compile"):
            pass
    trace_path = tmp_path / "trace-inst-a.jsonl"
    tracer.export_jsonl(trace_path)
    shards.append(trace_path)
    # torn line in one shard: dropped, never fatal
    with open(shards[0], "a") as f:
        f.write('{"kind": "count')

    report = merge_shards(shards)
    assert report["instances"] == ["inst-a", "inst-b"]
    assert report["dropped_lines"] == 1
    assert report["counters"]["spmv_cache_hits_total"] == 8.0
    g = report["gauges"]['spmv_avg_power_watts{fmt="csr"}']
    assert g == {"mean": 3.0, "min": 2.0, "max": 4.0, "instances": 2}
    hist = report["histograms"]["spmv_request_latency_seconds"]
    assert hist["count"] == 200
    assert hist["window_samples"] == 200  # percentiles over the CONCATENATED
    # windows, not averaged per-instance percentiles
    assert 0.001 <= hist["p50"] <= hist["p90"] <= hist["p99"] <= 0.1
    assert report["spans"]["total"] == 2
    assert report["spans"]["by_name"]["kernel.compile"]["count"] == 1


def test_aggregate_cli_writes_report(tmp_path):
    from repro.obs.aggregate import main

    reg = MetricsRegistry()
    reg.counter("spmv_cache_hits_total").inc()
    shard = tmp_path / "m.jsonl"
    reg.write_shard(shard, "solo")
    out = tmp_path / "report.json"
    assert main([str(shard), "-o", str(out)]) == 0
    report = json.loads(out.read_text())
    assert report["counters"]["spmv_cache_hits_total"] == 1.0


# ----------------------------------------------------------- http + serving


def _get(url: str) -> tuple[int, str]:
    with urllib.request.urlopen(url, timeout=10) as resp:
        return resp.status, resp.read().decode()


def test_http_scrape_surface():
    reg = MetricsRegistry()
    reg.counter("spmv_cache_hits_total").inc(2)
    srv = ObsHTTPServer(reg, extra=lambda: {"custom": 1}, port=0).start()
    try:
        code, body = _get(f"{srv.url}/metrics")
        assert code == 200
        assert "spmv_cache_hits_total 2" in body
        code, body = _get(f"{srv.url}/healthz")
        assert code == 200 and json.loads(body) == {"status": "ok"}
        code, body = _get(f"{srv.url}/obs")
        payload = json.loads(body)
        assert payload["custom"] == 1
        assert payload["metrics"]["counters"]["spmv_cache_hits_total"] == 2.0
        with pytest.raises(urllib.error.HTTPError):
            _get(f"{srv.url}/nope")
    finally:
        srv.stop()


def _serve(server, mats, objective="latency"):
    reqs = [
        SpmvRequest(
            rid=i,
            dense=m,
            x=np.random.default_rng(i).normal(size=m.shape[1]).astype(np.float32),
            objective=objective,
        )
        for i, m in enumerate(mats)
    ]
    return server.run(reqs)


def test_server_metrics_endpoint_e2e():
    """Acceptance: a served SpmvServer exposes Prometheus-parseable /metrics
    with cache hit/miss counters, latency quantiles, and energy gauges."""
    from repro.telemetry import AdaptiveFormatSelector, TelemetryRecorder

    clear_kernel_memo()
    session = AutoSpmvSession(
        _fake_tuner(),
        telemetry=TelemetryRecorder(),
        adaptive=AdaptiveFormatSelector(),
    )
    server = SpmvServer(session)
    _serve(server, [_mat(0), _mat(0), _mat(1)])
    srv = server.start_metrics_server(0)
    assert server.start_metrics_server(0) is srv  # idempotent
    try:
        code, body = _get(f"{srv.url}/metrics")
        assert code == 200
        for line in body.strip().splitlines():
            if not line.startswith("#"):
                assert _PROM_LINE.match(line), f"invalid line: {line!r}"
        assert re.search(r"spmv_cache_hits_total [1-9]", body)
        assert "spmv_cache_misses_total" in body
        assert re.search(
            r'spmv_request_latency_seconds\{objective="latency",quantile="0.5"\} '
            r"[0-9.eE+-]+",
            body,
        )
        assert 'quantile="0.99"' in body
        assert re.search(r'spmv_energy_joules_total\{fmt="[a-z]+"', body)
        assert re.search(r'spmv_avg_power_watts\{fmt="[a-z]+"', body)
    finally:
        server.stop_metrics_server()
    assert server._obs_http is None

    summary = server.summary()
    lat = summary["latency"]["latency"]
    assert lat["count"] == 3
    assert lat["p50"] <= lat["p99"]
    assert summary["energy"]  # per-format cells populated


def test_session_trace_monolithic_and_fused_paths(tmp_path):
    """Acceptance: the trace JSONL shows session.optimize -> kernel.compile
    nesting and a kernel.execute span for BOTH the monolithic and the
    partitioned serving paths."""
    tracer = get_tracer()
    clear_kernel_memo()
    session = AutoSpmvSession(_fake_tuner())
    dense = hetero_matrix(256)

    # monolithic compile-time path
    res = session.compile_time_optimize(dense, "latency")
    res.kernel(np.ones(dense.shape[1], np.float32))

    # partitioned path (one kernel per row block, run in sequence)
    part = session.partitioned_optimize(dense, "latency", max_blocks=4)
    part.kernel(np.ones(dense.shape[1], np.float32))

    path = tmp_path / "trace.jsonl"
    tracer.export_jsonl(path)
    spans = load_spans(path)
    by_id = {s["id"]: s for s in spans}

    def named(name, **attrs):
        return [
            s for s in spans
            if s["name"] == name
            and all((s.get("attrs") or {}).get(k) == v for k, v in attrs.items())
        ]

    mono = named("session.optimize", mode="compile")
    assert mono and mono[0]["attrs"]["cache_hit"] is False
    mono_children = span_children(spans, mono[0]["id"])
    assert {"cache.lookup", "plan.compute", "kernel.compile"} <= {
        s["name"] for s in mono_children
    }

    partitioned = named("session.optimize", mode="partitioned")
    assert partitioned
    assert "fused" not in partitioned[0]["attrs"]
    compiles = [
        s for s in spans
        if s["name"] == "kernel.compile" and s["parent"] == partitioned[0]["id"]
    ]
    # one compile per row block, each under its block's own format
    assert len(compiles) == part.n_blocks
    assert sorted(s["attrs"]["fmt"] for s in compiles) == sorted(part.formats)

    execs = named("kernel.execute", mode="partitioned")
    assert execs and execs[0]["attrs"]["n_blocks"] == part.n_blocks
    assert execs[0]["attrs"]["formats"] == "+".join(part.formats)
    # executions happen after optimize returned: roots, not optimize children
    for s in execs:
        assert s["parent"] is None or by_id[s["parent"]]["name"] != "session.optimize"


def test_cache_and_memo_counters_flow():
    clear_kernel_memo()
    reg = get_metrics()
    hits = reg.counter("spmv_cache_hits_total")
    misses = reg.counter("spmv_cache_misses_total")
    compiles = reg.counter("spmv_kernel_memo_compiles_total")
    memo_hits = reg.counter("spmv_kernel_memo_hits_total")
    h0, m0, c0, mh0 = hits.value, misses.value, compiles.value, memo_hits.value

    session = AutoSpmvSession(_fake_tuner())
    dense = random_matrix(128, 6.0, "fem", seed=5).astype(np.float32)
    session.compile_time_optimize(dense, "latency")
    assert misses.value == m0 + 1 and compiles.value == c0 + 1
    session.compile_time_optimize(dense, "latency")
    assert hits.value == h0 + 1 and memo_hits.value == mh0 + 1


def test_set_obs_enabled_gates_everything():
    set_obs_enabled(False)
    clear_kernel_memo()
    session = AutoSpmvSession(_fake_tuner())
    dense = random_matrix(96, 5.0, "fem", seed=6).astype(np.float32)
    session.compile_time_optimize(dense, "latency")
    assert get_tracer().spans() == []
    assert get_metrics().counter("spmv_cache_misses_total").value == 0.0
    set_obs_enabled(True)
