"""Run one cell of ``BENCHMARK.json`` on the chip and print its result.

    python -m chipbench.run --workload human_gene2.solve --seed 7 --seconds 30 --trace 0

One process, one cell, one run, from the root of a checkout. The cell names
a configuration (``chipbench/configs/<config>.json``: what the program is
asked to serve) and a traffic mix (``chipbench/traffic/<traffic>.json``),
whose ``driver`` names the module under ``chipbench/drivers/`` that makes
the inputs, sets the program up, answers one request and checks the
answers; ``chipbench.drivers`` states that contract. Set-up builds the
program objects once; the window then sends requests back to back, one
caller, until ``--seconds`` have passed, finishing the request in flight.
After the window every answer is compared with the driver's plain
reference against the cell's limits (``chipbench/limits/<cell>.json``),
one for each number the driver's ``check`` returns.

With ``--trace 0`` the result's metrics are the cell's end-to-end metrics;
with ``--trace 1`` the window runs under the profiler and the metrics are
the per-layer ones, each computed by ``chipbench/metrics/<metric>.py`` from
the reduced trace. Earlier stdout lines say what ran (the plan, requests,
SpMVs, compilations inside the window); the last stdout line is the JSON
result. The numbers compared and their limits are the last lines on
stderr. With no TPU, or fewer chips than the cell asks for, the run exits
non-zero and prints no result.

A new cell is new files only, found by the names in ``BENCHMARK.json``:

* ``chipbench/configs/<config>.json``: the configuration as it is run;
* ``chipbench/traffic/<traffic>.json``: the mix's parameters and its
  ``driver``;
* ``chipbench/drivers/<driver>.py``, where no driver serves the mix yet;
* ``chipbench/limits/<cell>.json``: ``limits``, one per name of the
  driver's ``CHECKS``, the ``readings`` they were set from
  (``python -m chipbench.readings``), and ``cpu_scale``, the size at which
  CPU tests run the cell and its control;
* ``chipbench/metrics/<metric>.py`` for each new per-layer metric: a
  ``read(ctx)`` that returns nothing where it finds nothing to read;
* its own tests under ``tests/chipbench/``, among them those of a new
  driver's input generator;
* its entries under ``configs``, ``workloads`` and the metrics of
  ``BENCHMARK.json``.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402

PKG = Path(__file__).resolve().parent
ROOT = PKG.parent
COMPILE_EVENTS = "/jax/core/compile/"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def say(*parts) -> None:
    print(*parts, flush=True)


# ------------------------------------------------------------------ the spec
def _read_json(path: Path) -> dict:
    return json.loads(path.read_text())


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list[dict]
    per_layer: list[dict]
    cpu_scale: float | None = None  # the size CPU tests run the cell at

    @property
    def driver(self):
        """The traffic's driver module, held to ``chipbench.drivers``' contract."""
        from chipbench.drivers import contract_faults

        driver = importlib.import_module(f"chipbench.drivers.{self.traffic['driver']}")
        faults = contract_faults(driver, self.limits)
        if faults:
            raise SystemExit(f"cell {self.name}: driver {self.traffic['driver']!r} breaks "
                             f"the contract: {'; '.join(faults)}")
        return driver


def load_cell(name: str) -> Cell:
    """Everything that belongs to one cell, found by the names in the spec."""
    spec = _read_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; expected one of {sorted(cells)}")
    w = cells[name]
    config = next(c for c in spec["configs"] if c["name"] == w["config"])

    def mine(metric):
        return name in metric.get("workloads", [name])

    e2e = [m for m in spec["end_to_end"] if mine(m)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"] if mine(m) and m["moves"] in e2e_names]
    limits = _read_json(PKG / "limits" / f"{name}.json")
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=_read_json(ROOT / config["file"]),
        traffic=_read_json(PKG / "traffic" / f"{w['traffic']}.json"),
        limits=limits["limits"],
        end_to_end=e2e,
        per_layer=per_layer,
        cpu_scale=limits.get("cpu_scale"),
    )


def load_reader(metric: str):
    """The ``read(ctx)`` function of one per-layer metric."""
    path = PKG / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{metric}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def import_program() -> None:
    """Put the checkout's program first on the path; refuse to run without it."""
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        raise SystemExit(f"chipbench: no program under {src}; run from a full checkout")
    sys.path.insert(0, str(src))


# --------------------------------------------------------------- the device
def device_info(chips: int, require_chip: bool = True) -> dict:
    import jax

    devices = jax.devices()
    dev = devices[0]
    if require_chip and dev.platform != "tpu":
        raise NoChip(f"no TPU found (JAX platform {dev.platform!r}); "
                     "this benchmark runs only on a chip")
    if require_chip and len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found {len(devices)}")
    return {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}


def memory_peak_bytes(chips: int) -> int | None:
    import jax

    peaks = []
    for dev in jax.devices()[:chips]:
        stats = dev.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


class CompileCounter:
    """Counts JAX's tracing and compilation events while ``active``."""

    def __init__(self):
        self.active = False
        self.events: dict[str, int] = {}

    def __call__(self, event: str, *args, **kwargs) -> None:
        if self.active and event.startswith(COMPILE_EVENTS):
            self.events[event] = self.events.get(event, 0) + 1


# ---------------------------------------------------------------- the window
@dataclass
class Window:
    answers: list = field(default_factory=list)
    spmvs: int = 0  # SpMVs answered to the caller
    seconds: float = 0.0
    request_s: list = field(default_factory=list)  # wall time of each request
    compiles: dict = field(default_factory=dict)
    program_spans: list = field(default_factory=list)


def run_window(program, seconds: float, trace_dir: Path | None = None) -> Window:
    """Closed loop, one caller: requests back to back for ``seconds``."""
    import jax

    from repro.obs.trace import get_tracer

    from chipbench.tracing import REQUEST, WINDOW

    counter = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    jax.monitoring.register_event_listener(counter)
    win = Window()
    tracer = get_tracer()
    tracer.clear()
    if trace_dir is not None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    counter.active = True
    try:
        with jax.profiler.TraceAnnotation(WINDOW):
            t0 = time.perf_counter()
            i = 0
            while time.perf_counter() - t0 < seconds:
                t_req = time.perf_counter()
                with jax.profiler.TraceAnnotation(REQUEST):
                    answer = program.request(i)
                win.request_s.append(time.perf_counter() - t_req)
                win.answers.append(answer)
                win.spmvs += answer.spmvs
                i += 1
            win.seconds = time.perf_counter() - t0
    finally:
        counter.active = False
        if trace_dir is not None:
            jax.profiler.stop_trace()
        jax.monitoring.unregister_event_duration_listener(counter)
        jax.monitoring.unregister_event_listener(counter)
    win.compiles = dict(counter.events)
    win.program_spans = tracer.spans()
    return win


# ---------------------------------------------------------------- the check
def decide(errors: list[dict], limits: dict) -> tuple[dict, int]:
    """The worst reading of each number, and the answers past a limit."""
    worst = {k: max((e[k] for e in errors), default=float("inf")) for k in limits}
    failed = sum(any(e[k] > lim for k, lim in limits.items()) for e in errors)
    return worst, failed


# ------------------------------------------------------------------ metrics
@dataclass
class TraceContext:
    """What a per-layer reader sees of a traced run."""

    reduction: object  # chipbench.tracing.Reduction
    spmvs: int  # SpMVs answered in the window
    least_s: float  # shortest possible SpMV on this device (roofline)


def per_layer_values(cell: Cell, ctx: TraceContext) -> dict:
    out = {}
    for m in cell.per_layer:
        value = load_reader(m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, scale: float = 1.0, tuner=None,
             cell: Cell | None = None) -> dict:
    """One run of one cell; returns the result object (``checks`` last).

    Tests pass ``require_chip=False`` and a small ``scale`` and ``tuner``
    to drive the same path on the CPU, and may pass a prepared ``cell`` in
    place of the one the spec names ``workload``."""
    if cell is None:
        cell = load_cell(workload)
    import_program()
    device = device_info(cell.chips, require_chip)
    say(f"chipbench: {workload} seed {seed} on {device['count']} x {device['kind']}")

    import jax

    from repro.utils.compile_cache import configure_compile_cache

    from chipbench.roofline import least_seconds, peaks

    peak = peaks(device["kind"]) if require_chip else None
    say(f"compile cache: {configure_compile_cache()}")
    # cache every program, the short ones too, so only a checkout's first
    # run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    driver = cell.driver
    if tuner is None:
        tuner = driver.build_tuner(cell.traffic)
    program = driver.Program(cell.config, cell.traffic, seed, tuner, scale)
    say(f"{program.about}; set-up metrics {program.metrics}")
    tdir = Path(tempfile.mkdtemp(prefix="chipbench_trace_")) if trace else None
    setup_s = time.perf_counter() - T_PROCESS
    win = run_window(program, seconds, tdir)
    say(f"window: {len(win.answers)} requests, {win.spmvs} SpMVs in {win.seconds:.3f}s; "
        f"compilations inside the window: {sum(win.compiles.values())} {win.compiles}")
    say(f"request seconds: {[round(t, 3) for t in win.request_s]}")
    device["memory_peak_bytes"] = memory_peak_bytes(cell.chips)

    result: dict = {"correct": False, "attempted": len(win.answers), "failed": 0}
    if trace:
        from chipbench import tracing

        red = tracing.reduce(tracing.load(tdir), win.program_spans)
        shutil.rmtree(tdir, ignore_errors=True)
        least = (least_seconds(program.nnz, program.n_rows, program.n_cols, peak)
                 if peak else float("nan"))
        metrics = per_layer_values(cell, TraceContext(red, win.spmvs, least))
        device["busy_s"] = red.busy_ns / 1e9
        device["window_s"] = red.window_ns / 1e9
        say(f"trace: {red.spmv_calls} SpMV programs for {win.spmvs} SpMVs answered, "
            f"kernel {red.kernel_ns / 1e6:.3f} ms, other ops {red.xla_ns / 1e6:.3f} ms, "
            f"busy {red.busy_ns / 1e9:.3f}s of {red.window_ns / 1e9:.3f}s")
        breakdown = {"device_ops": [list(x) for x in red.device_ops],
                     "idle_gaps": [list(x) for x in red.idle_gaps]}
    else:
        metrics = {"spmv_ms": {"value": win.seconds * 1e3 / max(win.spmvs, 1), "unit": "ms"}}
        metrics.update({k: {"value": v, "unit": "s"} for k, v in program.metrics.items()})
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
        wanted = {m["name"] for m in cell.end_to_end}
        metrics = {k: v for k, v in metrics.items() if k in wanted}
        breakdown = None

    # free the program's state before the reference runs
    inputs = program.release()
    del program, tuner
    errors = driver.check(inputs, win.answers, cell.traffic)
    worst, result["failed"] = decide(errors, cell.limits)
    result["correct"] = bool(win.answers) and result["failed"] == 0
    result["metrics"] = metrics
    result["device"] = device
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": worst[k], "limit": lim} for k, lim in cell.limits.items()}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    except NoChip as exc:
        print(f"chipbench: {exc}", file=sys.stderr)
        return 3
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
