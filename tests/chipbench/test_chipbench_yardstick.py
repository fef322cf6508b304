"""The benchmark's generator copy, byte counts, peaks and spec files."""

import importlib
import json
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench import drivers, matrices, roofline, run

SPEC = json.loads((run.ROOT / "BENCHMARK.json").read_text())
CONFIGS = {c["name"]: json.loads((run.ROOT / c["file"]).read_text()) for c in SPEC["configs"]}


def _driver_of(workload: dict):
    traffic = json.loads((run.PKG / "traffic" / f"{workload['traffic']}.json").read_text())
    return importlib.import_module(f"chipbench.drivers.{traffic['driver']}")


# the configurations whose cells take their inputs from chipbench.matrices;
# a driver with a generator of its own brings that generator's tests
MATRIX_CONFIGS = sorted({w["config"] for w in SPEC["workloads"]
                         if getattr(_driver_of(w), "inputs", None) is matrices.inputs})


@pytest.mark.parametrize("name", MATRIX_CONFIGS)
def test_generator_copy_matches_the_program_byte_for_byte(name):
    from repro.sparse.generate import SUITE, generate_by_name

    cfg = CONFIGS[name]
    spec = SUITE[cfg["matrix"]["name"]]
    assert (spec.n, spec.nnz, spec.pattern, spec.seed) == tuple(
        cfg["matrix"][k] for k in ("n", "nnz", "pattern", "seed"))
    ours = matrices.program_copy(cfg["matrix"], scale=0.02)
    theirs = generate_by_name(cfg["matrix"]["name"], scale=0.02)
    assert ours.dtype == theirs.dtype == np.float32
    assert ours.tobytes() == theirs.tobytes()


@pytest.mark.parametrize("name", MATRIX_CONFIGS)
def test_seed_draws_the_values_and_keeps_the_pattern(name):
    m = CONFIGS[name]["matrix"]
    a = matrices.generate(m, 2**31 + 11, scale=0.02)
    b = matrices.generate(m, 2**31 + 12, scale=0.02)
    again = matrices.generate(m, 2**31 + 11, scale=0.02)
    assert np.array_equal(a != 0, b != 0)
    assert not np.array_equal(a, b)
    assert np.array_equal(a, again)
    assert a[a != 0].min() >= 0.1


@pytest.mark.parametrize("name", MATRIX_CONFIGS)
@pytest.mark.parametrize("scale", [0.01, 0.05])
def test_generate_holds_the_published_count(name, scale):
    m = CONFIGS[name]["matrix"]
    dense = matrices.generate(m, 2**40 + 9, scale=scale)
    assert dense.dtype == np.float32 and dense.flags.writeable and dense.flags.c_contiguous
    assert np.count_nonzero(dense) == matrices.entries(m, scale)
    if m.get("symmetric"):
        assert np.array_equal(dense, dense.T)
        assert np.all(np.diag(dense) != 0)
        assert np.count_nonzero(np.tril(dense)) == matrices.stored(m, scale)


@pytest.mark.parametrize("name, published, entries", [
    ("human_gene2", 9_041_364, 18_068_388), ("rim", 1_014_951, 1_014_951)])
def test_entries_at_published_size(name, published, entries):
    m = CONFIGS[name]["matrix"]
    assert matrices.stored(m) == m["nnz"] == published
    assert matrices.entries(m) == entries


def test_start_vectors_differ_by_solve_and_stream():
    v = [matrices.start_vector(50, 7, i) for i in range(2)]
    assert not np.allclose(v[0], v[1])
    assert not np.allclose(v[0], matrices.start_vector(50, 7, 0, stream=2))
    assert np.array_equal(v[0], matrices.start_vector(50, 7, 0))


def test_compulsory_bytes_on_a_hand_counted_matrix():
    # 3 x 4, five nonzeros: 5 values, 4 entries of x, 3 of y, 4 bytes each
    dense = np.array([[1, 0, 2, 0], [0, 0, 0, 3], [4, 5, 0, 0]], np.float32)
    nnz = int(np.count_nonzero(dense))
    assert roofline.compulsory_bytes(nnz, *dense.shape) == 4 * (5 + 4 + 3) == 48


@pytest.mark.parametrize("name, expected", [("human_gene2", 72_388_272), ("rim", 4_240_284)])
def test_compulsory_bytes_of_the_cells(name, expected):
    m = CONFIGS[name]["matrix"]
    assert roofline.compulsory_bytes(matrices.entries(m), m["n"], m["n"]) == expected


def test_least_time_is_bound_by_bytes_on_a_v5e():
    peak = roofline.peaks("TPU v5 lite")
    assert peak["hbm_bytes_per_s"] == 819e9
    assert roofline.least_seconds(18_068_388, 14_340, 14_340, peak) == pytest.approx(
        72_388_272 / 819e9)


def test_an_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        roofline.peaks("TPU v99")


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_finds_its_files(cell):
    c = run.load_cell(cell)
    assert c.config["name"] == cell.split(".")[0]
    assert {m["name"] for m in c.end_to_end} >= {"setup_s", "spmv_ms", "tune_s"}
    assert c.per_layer
    for m in c.per_layer:
        assert callable(run.load_reader(m["name"]))
    driver = importlib.import_module(f"chipbench.drivers.{c.traffic['driver']}")
    assert drivers.contract_faults(driver, c.limits) == []
    assert c.driver is driver
    assert set(c.limits) == set(driver.CHECKS)
    assert 0 < c.cpu_scale <= 1


def _partial_driver(checks):
    """A driver that keeps only part of the contract."""

    class Program:
        def request(self, i):
            return None

    return SimpleNamespace(CHECKS=checks, inputs=lambda config, seed, scale: None,
                           Program=Program)


def test_a_driver_that_breaks_the_contract_is_named():
    missing = ["no callable build_tuner", "no callable check", "no callable control",
               "Program has no method release"]
    assert drivers.contract_faults(_partial_driver(["y_rel_err"]), {"y_rel_err": 0.0}) == [
        *missing, "CHECKS is not a tuple of names"]
    assert drivers.contract_faults(_partial_driver(("y_rel_err",)),
                                   {"y_rel_err": 0.0, "z": 0.0}) == [
        *missing, "the limits name ['y_rel_err', 'z'], CHECKS ['y_rel_err']"]


def test_a_cell_whose_limits_differ_from_its_checks_is_refused():
    from dataclasses import replace

    c = run.load_cell("rim.solve")
    broken = replace(c, limits={**c.limits, "answered_short": 0.0})
    with pytest.raises(SystemExit, match="breaks the contract.*answered_short"):
        broken.driver
