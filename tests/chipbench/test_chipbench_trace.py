"""The trace reduction and the per-layer readers on a small recorded trace.

The trace is written out as an XSpace (the profiler's own format) with one
TPU plane and one host plane, so ``tracing.load`` reads it as it reads a
chip's trace. Times are in nanoseconds from the profile's start:

* window annotation 0..100,000; request annotations 10,000..60,000 and
  70,000..95,000;
* two SpMV programs (``jit__jitted_spmv``) at 20,000..30,000 and
  40,000..50,000, each holding a 6,000 ns gather, a 3,000 ns Mosaic kernel
  and a 500 ns reduce whose HLO names the kernel but is XLA's own; one
  unrelated op at 80,000..81,000.

A second trace adds another program (``jit_cg_step``) at 96,000..104,000,
past the window's end, around a Mosaic kernel at 97,000..103,000: the way a
solve whose recurrence runs on the device holds its SpMV.

Device op events carry the op's HLO text as their name, as a TPU's do.
"""

import pytest

from chipbench import run, tracing

START = 1_700_000_000_000_000_000


def _xspace(cg_step: bool = False) -> str:
    names = ["jit__jitted_spmv(1)",
             r"%fusion.1 = f32[96]{0} fusion(f32[8]{0} %x, s32[96]{0} %idx), kind=kCustom",
             r"%csr_spmv.1 = f32[3,1,8]{2,1,0} custom-call(s32[3]{0} %m), "
             r'custom_call_target=\"tpu_custom_call\"',
             r"%add.1 = f32[8]{0} add(f32[8]{0} %a, f32[8]{0} %b)",
             tracing.WINDOW, tracing.REQUEST,
             r"%reduce.1 = f32[3,8]{1,0} reduce(f32[3,1,8]{2,1,0} %csr_spmv.1), "
             r"to_apply=%csr_spmv.reduce_sub_computation",
             "jit_cg_step(2)",
             r"%cg_spmv.2 = f32[8]{0} custom-call(f32[8]{0} %p), "
             r'custom_call_target=\"tpu_custom_call\"']
    meta = "\n".join(
        f'  event_metadata {{ key: {i + 1} value {{ id: {i + 1} name: "{n}" }} }}'
        for i, n in enumerate(names)
    )

    def ev(mid, start, dur):
        return f"    events {{ metadata_id: {mid} offset_ps: {start * 1000} duration_ps: {dur * 1000} }}"

    modules = [ev(1, 20_000, 10_000), ev(1, 40_000, 10_000)]
    ops = [ev(2, 20_500, 6_000), ev(3, 26_500, 3_000), ev(7, 29_500, 500),
           ev(2, 40_500, 6_000), ev(3, 46_500, 3_000), ev(7, 49_500, 500),
           ev(4, 80_000, 1_000)]
    if cg_step:
        modules.append(ev(8, 96_000, 8_000))
        ops.append(ev(9, 97_000, 6_000))
    modules, ops = "\n".join(modules), "\n".join(ops)
    host = "\n".join([ev(5, 0, 100_000), ev(6, 10_000, 50_000), ev(6, 70_000, 25_000)])
    return f"""
planes {{
  id: 1
  name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0
{modules}
  }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0
{ops}
  }}
{meta}
}}
planes {{
  id: 2
  name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0
{host}
  }}
{meta}
}}
planes {{
  id: 3
  name: "Task Environment"
  stats {{ metadata_id: 1 uint64_value: {START} }}
  stat_metadata {{ key: 1 value {{ id: 1 name: "profile_start_time" }} }}
}}
"""


def _load(tmp_path_factory, cg_step: bool = False) -> tracing.Trace:
    import jax

    d = tmp_path_factory.mktemp("trace")
    out = d / "plugins" / "profile" / "run" / "host.xplane.pb"
    out.parent.mkdir(parents=True)
    out.write_bytes(jax.profiler.ProfileData.text_proto_to_serialized_xspace(_xspace(cg_step)))
    return tracing.load(d)


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    return _load(tmp_path_factory)


# one program span, 60,000..68,000 ns on the trace's clock
PROGRAM_SPANS = [{"name": "session.optimize", "ts": (START + 60_000) / 1e9, "dur_s": 8e-6}]


@pytest.fixture(scope="module")
def reduction(trace):
    return tracing.reduce(trace, PROGRAM_SPANS)


@pytest.fixture(scope="module")
def cg_trace(tmp_path_factory):
    return _load(tmp_path_factory, cg_step=True)


@pytest.fixture(scope="module")
def cg_reduction(cg_trace):
    return tracing.reduce(cg_trace, PROGRAM_SPANS)


def test_reduction_counts_programs_kernels_and_busy_time(reduction):
    r = reduction
    assert r.window_ns == 100_000
    assert r.spmv_calls == 2
    assert r.kernel_ns == 6_000
    assert r.xla_ns == 13_000
    assert r.busy_ns == 20_000  # 2 x (6,000 + 3,000 + 500) + 1,000
    assert r.request_ns == 75_000
    assert r.request_idle_ns == 75_000 - 19_000 - 1_000


def _ctx(reduction, spmvs=2, least_s=1e-6):
    return run.TraceContext(reduction, spmvs, least_s)


@pytest.mark.parametrize("metric, expected", [
    ("kernel_ms.solve", 3_000 / 1e6),
    ("xla_ms.solve", 6_500 / 1e6),
    ("spmv_roofline.solve", 100.0 * 1_000 / 9_500),
    ("host_ms.solve", 55_000 / 2 / 1e6),
    ("idle_share.solve", 80.0),
])
def test_reader_values(reduction, metric, expected):
    assert run.load_reader(metric)(_ctx(reduction)) == pytest.approx(expected)


def test_readers_return_nothing_without_spmv_programs(reduction):
    from dataclasses import replace

    empty = replace(reduction, spmv_calls=0, kernel_ns=0.0, xla_ns=0.0)
    for metric in ("kernel_ms.solve", "xla_ms.solve", "spmv_roofline.solve"):
        assert run.load_reader(metric)(_ctx(empty)) is None


def test_idle_gaps_are_labelled_by_the_innermost_open_span(reduction):
    # idle: 0..20,500 and 30,000..40,500 (middles in the first solve),
    # 50,000..80,000 (middle 65,000, inside the program's span, which is
    # shorter than the solve around it) and 81,000..100,000 (second solve)
    labels = [label for label, _ in reduction.idle_gaps]
    seconds = [s for _, s in reduction.idle_gaps]
    assert labels == ["session.optimize", tracing.REQUEST, tracing.REQUEST, tracing.REQUEST]
    assert seconds == pytest.approx([30e-6, 20.5e-6, 19e-6, 10.5e-6])


def test_device_ops_longest_first(reduction):
    names = [name for name, _ in reduction.device_ops]
    assert names[:2] == ["fusion.1", "csr_spmv.1"]
    assert sorted(names[2:]) == ["add.1", "reduce.1"]  # 1,000 ns each
    assert reduction.device_ops[0][1] == pytest.approx(12_000 / 1e9)


def test_every_op_and_module_in_the_window_is_timed_by_name(reduction):
    assert reduction.op_ns == {"fusion.1": 12_000, "csr_spmv.1": 6_000, "reduce.1": 1_000,
                               "add.1": 1_000}
    assert reduction.device_ops == [(name, ns / 1e9) for name, ns in sorted(
        reduction.op_ns.items(), key=lambda kv: -kv[1])]
    assert reduction.module_ns == {"jit__jitted_spmv(1)": 20_000}
    assert reduction.devices == 1


def test_a_kernel_in_another_program_is_timed_but_not_counted_as_spmv(reduction, cg_trace,
                                                                     cg_reduction):
    (op,) = [e for e in cg_trace.devices["/device:TPU:0"][tracing.OPS_LINE]
             if e.short == "cg_spmv.2"]
    assert tracing.KERNEL_MARK in op.name
    r = cg_reduction
    # clipped to the window, which ends at 100,000
    assert r.op_ns["cg_spmv.2"] == 3_000
    assert r.module_ns["jit_cg_step(2)"] == 4_000
    assert r.busy_ns == 23_000
    assert (r.kernel_ns, r.xla_ns, r.spmv_calls) == (6_000, 13_000, 2)
    for metric in ("kernel_ms.solve", "xla_ms.solve", "spmv_roofline.solve"):
        reader = run.load_reader(metric)
        assert reader(_ctx(r)) == reader(_ctx(reduction))


def test_a_trace_without_the_window_is_refused(trace):
    from dataclasses import replace

    with pytest.raises(ValueError, match="chipbench.window"):
        tracing.reduce(replace(trace, host=[e for e in trace.host if e.name != tracing.WINDOW]))


def test_union_merges_and_clips():
    assert tracing.union([(5, 8), (0, 3), (2, 4), (9, 20)], 1, 10) == [(1, 4), (5, 8), (9, 10)]
    assert tracing.covered([(1, 4), (5, 8)], 3, 6) == 2
