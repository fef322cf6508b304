"""The process-wide kernel memo's one insert-and-evict path: SpMV, SpMSpV
and partitioned-block entries share its LRU order, its bound and its
counters."""

import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.common import DEFAULT_SCHEDULE
from repro.kernels.ops import (
    clear_kernel_memo,
    compile_spmspv,
    compile_spmv,
    evict_kernel_memo_format,
    kernel_memo_limit,
    kernel_memo_stats,
    kernel_memoized,
    set_kernel_memo_limit,
)
from repro.obs.metrics import get_metrics
from repro.partition import compile_partitioned
from repro.sparse.generate import random_matrix

from tests.test_partition import forced_plan, hetero_matrix


@pytest.fixture
def memo():
    clear_kernel_memo()
    limit = kernel_memo_limit()
    yield
    set_kernel_memo_limit(limit)
    clear_kernel_memo()


def test_shrinking_the_limit_evicts_spmv_and_spmspv_in_lru_order(memo):
    m0, m1 = (random_matrix(96, 4.0, "fem", seed=s).astype(np.float32) for s in (1, 2))
    compile_spmv(m0, "csr", memo_key="m0")
    compile_spmspv(m0, memo_key="m0")
    compile_spmv(m1, "csr", memo_key="m1")
    compile_spmspv(m1, memo_key="m1")
    before = kernel_memo_stats()
    process_evictions = get_metrics().counter("spmv_kernel_memo_evictions_total").value
    compile_spmv(m0, "csr", memo_key="m0")  # a hit: now the most recent

    set_kernel_memo_limit(2)
    after = kernel_memo_stats()
    assert after["hits"] == before["hits"] + 1
    assert after["compiles"] == before["compiles"]
    # the least recently used go first, whatever path inserted them
    assert list(ops._KERNEL_MEMO) == [
        ("m1", "spmspv", DEFAULT_SCHEDULE),
        ("m0", "csr", DEFAULT_SCHEDULE),
    ]
    assert after["evictions"] == before["evictions"] + 2
    assert (
        get_metrics().counter("spmv_kernel_memo_evictions_total").value
        == process_evictions + 2
    )

    compile_spmspv(m0, memo_key="m0")  # evicted: compiles again, evicts one
    final = kernel_memo_stats()
    assert final["compiles"] == after["compiles"] + 1
    assert final["evictions"] == after["evictions"] + 1
    assert not kernel_memoized("m1", "spmspv")
    assert list(ops._KERNEL_MEMO)[0] == ("m0", "csr", DEFAULT_SCHEDULE)


def test_evicting_a_format_drops_only_its_partitioned_blocks(memo):
    dense = hetero_matrix(256)
    plan = forced_plan(dense, ["csr", "ell"], 4)
    first = compile_partitioned(dense, plan, memo_key="hetero")
    block_key = {b.index: ("hetero", b.row_start, b.row_end) for b in first.blocks}
    assert [b.fmt for b in first.blocks] == ["csr", "ell", "csr", "ell"]
    before = kernel_memo_stats()

    assert evict_kernel_memo_format("ell") == 2
    assert kernel_memo_stats()["evictions"] == before["evictions"] + 2
    for b in first.blocks:
        assert kernel_memoized(block_key[b.index], b.fmt) == (b.fmt == "csr")

    again = compile_partitioned(dense, plan, memo_key="hetero")
    after = kernel_memo_stats()
    assert after["hits"] == before["hits"] + 2
    assert after["compiles"] == before["compiles"] + 2
    for old, new in zip(first.blocks, again.blocks):
        assert (new.kernel is old.kernel) == (old.fmt == "csr")
