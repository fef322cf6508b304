"""AOT compiles of the Pallas kernels for a described TPU v5e chip.

Each test lowers a kernel with ``interpret=False`` and compiles it with the
TPU compiler for a ``v5e:2x2`` topology that is described, not attached, at
the chip smoke's real shapes: ``human_gene2`` (n = 14,340, ~9.0M nnz, row
counts up to 1,426 before duplicate collapse). A compile that passes is not
a chip run; it proves Mosaic accepts the kernel at that shape and schedule.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and the test workers all
import this file.
"""

import itertools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.kernels.bell import bell_spmv_pallas
from repro.kernels.common import (
    GATHER_SCOPE,
    NNZ_TILE_CHOICES,
    ROWS_PER_BLOCK_CHOICES,
    KernelSchedule,
    ceil_to,
    default_interpret,
)
from repro.kernels.csr import PREFETCH_SMEM_BYTES, WALK_ROWS, csr_spmv_pallas
from repro.kernels.ell import ell_spmm_pallas, ell_spmv_pallas
from repro.kernels.sell import sell_spmv_pallas
from repro.kernels.spmspv import csc_spmspv_pallas
from repro.sparse.generate import SUITE
from repro.sparse.registry import unregister_format

N = SUITE["human_gene2"].n  # 14,340
NNZ = SUITE["human_gene2"].nnz  # 9,041,364
MAX_ROW = 1426  # largest drawn row count of the generated matrix
BELL_BLOCKS = 113  # every 128-column block of every block-row is occupied

# the extremes of both schedule axes plus the default rows_per_block (the
# whole space compiles; its corners guard it at a fraction of the time)
GEOMETRY = list(
    itertools.product(
        (ROWS_PER_BLOCK_CHOICES[0], 64, ROWS_PER_BLOCK_CHOICES[-1]),
        (NNZ_TILE_CHOICES[0], NNZ_TILE_CHOICES[-1]),
    )
)
NUMERICS = [
    dict(unroll=8),
    dict(accum_dtype="bfloat16"),
    dict(unroll=4, accum_dtype="bfloat16", dimension_semantics="parallel"),
]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """Described-chip compiles cannot be read back from the persistent
    cache without a chip; keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _csr(sharding, sched, n=N, gather=None):
    rpb, nt = sched.rows_per_block, sched.nnz_tile
    nnz_pad = ceil_to(NNZ + -(-n // rpb) * nt, nt)  # worst-case block padding
    return _compile(
        lambda d, c, r, x: csr_spmv_pallas(
            d, c, r, x, n, (rpb, nt), sched, gather=gather, interpret=False
        ),
        _sds(sharding, (nnz_pad,)),
        _sds(sharding, (nnz_pad,), jnp.int32),
        _sds(sharding, (nnz_pad,), jnp.int32),
        _sds(sharding, (n,)),
    )


def _ell(sharding, sched):
    R, W = ceil_to(N, sched.rows_per_block), ceil_to(MAX_ROW, sched.nnz_tile)
    _compile(
        lambda d, c, x: ell_spmv_pallas(d, c, x, sched, interpret=False),
        _sds(sharding, (R, W)),
        _sds(sharding, (R, W), jnp.int32),
        _sds(sharding, (N,)),
    )


def _sell(sharding, sched):
    C, nt = sched.rows_per_block, sched.nnz_tile
    n_slices = -(-N // C)
    rows = ceil_to(NNZ // C + n_slices * nt, nt)
    _compile(
        lambda d, c, w, x: sell_spmv_pallas(d, c, w, x, sched, interpret=False),
        _sds(sharding, (rows, C)),
        _sds(sharding, (rows, C), jnp.int32),
        _sds(sharding, (n_slices,), jnp.int32),
        _sds(sharding, (N,)),
    )


def _bell(sharding, sched):
    br = min(sched.rows_per_block, 256)
    nbr = -(-N // br)
    _compile(
        lambda d, b, x: bell_spmv_pallas(d, b, x, sched, interpret=False),
        _sds(sharding, (nbr, BELL_BLOCKS, br, 128)),
        _sds(sharding, (nbr, BELL_BLOCKS), jnp.int32),
        _sds(sharding, (N,)),
    )


SEED_KERNELS = {"csr": _csr, "ell": _ell, "sell": _sell, "bell": _bell}


@pytest.mark.parametrize("rpb,nt", GEOMETRY)
@pytest.mark.parametrize("fmt", ["csr", "ell", "sell"])
def test_seed_kernel_compiles_over_geometry(one_chip, fmt, rpb, nt):
    SEED_KERNELS[fmt](one_chip, KernelSchedule(rows_per_block=rpb, nnz_tile=nt))


@pytest.mark.parametrize("rpb", ROWS_PER_BLOCK_CHOICES)
def test_bell_compiles_over_block_rows(one_chip, rpb):
    _bell(one_chip, KernelSchedule(rows_per_block=rpb))


@pytest.mark.parametrize("numerics", NUMERICS, ids=lambda d: "-".join(map(str, d.values())))
@pytest.mark.parametrize("fmt", sorted(SEED_KERNELS))
def test_seed_kernel_compiles_over_numerics(one_chip, fmt, numerics):
    SEED_KERNELS[fmt](one_chip, KernelSchedule(**numerics))


@pytest.mark.parametrize("gather", ["vmem", "xla"])
@pytest.mark.parametrize("nt", [NNZ_TILE_CHOICES[0], NNZ_TILE_CHOICES[-1]])
@pytest.mark.parametrize("n", [N, SUITE["rim"].n], ids=["human_gene2", "rim"])
def test_csr_gather_of_x_compiles_at_the_cells_widths(one_chip, n, nt, gather):
    """x of R = 113 (human_gene2) and 177 (rim) rows of 128, gathered in
    the kernel from VMEM or by XLA before the launch."""
    sched = KernelSchedule(rows_per_block=8, nnz_tile=nt)
    text = _csr(one_chip, sched, n=n, gather=gather).as_text()
    assert (GATHER_SCOPE in text) == (gather == "xla")


@pytest.mark.parametrize("window", [True, False], ids=["window", "no-window"])
@pytest.mark.parametrize("rpb,nt,stored", [(512, 1024, 31_070_208), (128, 128, 29_998_336)])
def test_csr_compiles_at_the_hpcg_cells_shape(one_chip, rpb, nt, stored, window):
    """HPCG's 104^3 stencil (1,124,864 rows) at two storable tilings and
    their stream lengths. x is past the bound of a walk over all of it, so
    without a window XLA gathers it. The stream's window (a tile spans at
    most 173 rows of 128: 3 steps) brings it into the kernel where its
    starts fit SMEM beside the tile map: 30,342 tiles of 1,024, not 234,362
    of 128, which ``x_window`` gives no window."""
    n = 104**3
    n_tiles = stored // nt
    windowed = window and 2 * n_tiles * 4 <= PREFETCH_SMEM_BYTES
    sched = KernelSchedule(rows_per_block=rpb, nnz_tile=nt, unroll=8)
    text = _compile(
        lambda d, c, r, x, s: csr_spmv_pallas(
            d, c, r, x, n, (rpb, nt), sched, interpret=False,
            **(dict(x_start=s, x_walk=3 * WALK_ROWS) if windowed else {}),
        ),
        _sds(one_chip, (stored,)),
        _sds(one_chip, (stored,), jnp.int32),
        _sds(one_chip, (stored,), jnp.int32),
        _sds(one_chip, (n,)),
        _sds(one_chip, (n_tiles,), jnp.int32),
    ).as_text()
    assert (GATHER_SCOPE in text) == (not windowed)


@pytest.fixture()
def bcsr_spmv_pallas():
    """The BCSR kernel, with the plugin registered for this test only: the
    worker's later test files must see the seed formats alone."""
    from repro.sparse import bcsr

    bcsr.register()
    yield bcsr.bcsr_spmv_pallas
    unregister_format("bcsr")


@pytest.mark.parametrize("rpb", [8, 64])
def test_bcsr_compiles(one_chip, bcsr_spmv_pallas, rpb):
    nb, sched = 40_000, KernelSchedule(rows_per_block=rpb)
    _compile(
        lambda d, c, r, x: bcsr_spmv_pallas(
            d, c, r, x, -(-N // rpb), sched, interpret=False
        ),
        _sds(one_chip, (nb, rpb, 128)),
        _sds(one_chip, (nb,), jnp.int32),
        _sds(one_chip, (nb,), jnp.int32),
        _sds(one_chip, (N,)),
    )


def test_ell_spmm_compiles(one_chip):
    """SpMM gathers an (R, W, k) operand, nnz * k * 4 B, so it is compiled at
    the sparse-decode shape it serves: a 50%-pruned 3072 x 1024 FFN weight
    (qwen3-0.6b's published widths) against a 128-wide panel of vectors."""
    sched = KernelSchedule()
    R, W, k = 3072, 512, 128
    _compile(
        lambda d, c, X: ell_spmm_pallas(d, c, X, sched, interpret=False),
        _sds(one_chip, (R, W)),
        _sds(one_chip, (R, W), jnp.int32),
        _sds(one_chip, (1024, k)),
    )


def test_sharded_ell_compiles_on_four_chips(topo):
    """The SPMD executor's program: one ELL plane block per chip, X
    replicated (``partition/executor.py``)."""
    from jax.sharding import Mesh

    sched = KernelSchedule()
    mesh = Mesh(topo.devices[:4], ("data",))
    R, W = ceil_to(N // 4 + 64, sched.rows_per_block), ceil_to(MAX_ROW, sched.nnz_tile)
    planes, rep = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())

    def body(d, c, x):
        return ell_spmv_pallas(d[0], c[0], x, sched, interpret=False)[None]

    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P("data"), P("data"), P()),
        out_specs=P("data"),
        check_vma=False,
    )
    compiled = _compile(
        fn,
        _sds(planes, (4, R, W)),
        _sds(planes, (4, R, W), jnp.int32),
        _sds(rep, (N,)),
    )
    per_device = compiled.memory_analysis().argument_size_in_bytes
    assert per_device < 4 * R * W * 8  # the planes are split, not replicated


def test_scatter_kernels_refuse_to_compile():
    """SpMSpV scatter-adds by unsorted row id, which Mosaic cannot lower:
    asked for a compiled kernel, it raises, never interprets."""
    sched = KernelSchedule()
    with pytest.raises(NotImplementedError, match="csc_spmspv"):
        csc_spmspv_pallas(
            jnp.zeros((9, 128)), jnp.zeros((9, 128), jnp.int32),
            jnp.zeros(8, jnp.int32), jnp.zeros(8), 8, sched, interpret=False,
        )


def test_cpu_backend_resolves_to_interpret():
    assert jax.default_backend() == "cpu"
    assert default_interpret() is True


def test_compile_cache_dir_from_env_else_fixed_in_checkout(monkeypatch, tmp_path):
    from repro.utils import compile_cache

    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
        assert compile_cache.configure_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == prev  # JAX reads the env
        monkeypatch.delenv(compile_cache.ENV_VAR)
        path = compile_cache.configure_compile_cache()
        assert path == str(compile_cache.CHECKOUT_ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert compile_cache.configure_compile_cache() == path  # not per process
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
