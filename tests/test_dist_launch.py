"""Tests for sharding rules, HLO collective parsing, input specs, and the
roofline math (the dry-run pieces that don't need 512 devices)."""

import jax
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import ARCH_IDS, SHAPES, applicable, cells_for, get_config
from repro.dist.sharding import spec_for
from repro.launch.hlo_analysis import (
    CollectiveOp,
    parse_collectives,
    summarize_collectives,
)
from repro.launch.specs import model_flops, train_batch_specs

MESH1 = AbstractMesh((16, 16), ("data", "model"))
MESH2 = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


# ------------------------------------------------------------- sharding rules
def test_fsdp_tp_weight_sharding():
    # llama3 W_q: (d_model, heads, head_dim)
    spec = spec_for(MESH1, (4096, 32, 128), ("embed", "heads", None))
    assert spec == P("data", "model")
    spec2 = spec_for(MESH2, (4096, 32, 128), ("embed", "heads", None))
    assert spec2 == P(("pod", "data"), "model")


def test_kv_heads_replicated_when_indivisible():
    # kv=8 on a 16-way model axis -> replicated (kv-repeat convention)
    spec = spec_for(MESH1, (4096, 8, 128), ("embed", "kv", None))
    assert spec == P("data")
    # kv=32 divides -> sharded
    spec = spec_for(MESH1, (4096, 32, 128), ("embed", "kv", None))
    assert spec == P("data", "model")


def test_duplicate_axis_not_reused():
    # sLSTM w_down: ("embed", "embed") — second occurrence must replicate
    spec = spec_for(MESH1, (2048, 2048), ("embed", "embed"))
    assert spec == P("data")


def test_vocab_sharding():
    spec = spec_for(MESH1, (128256, 4096), ("vocab", "embed"))
    assert spec == P("model", "data")


def test_indivisible_batch_replicated():
    spec = spec_for(MESH2, (1, 128), ("batch", None))  # long_500k batch=1
    assert spec == P()


# --------------------------------------------------------------- HLO analysis
HLO_SAMPLE = """
  %all-gather.1 = bf16[16,512]{1,0} all-gather(bf16[16,32]{1,0} %p0), channel_id=1, replica_groups=[16,16]<=[256], dimensions={1}
  %all-reduce.2 = f32[128,64]{1,0} all-reduce(f32[128,64]{1,0} %p1), channel_id=2, replica_groups={{0,1,2,3}}, to_apply=%add
  %reduce-scatter.3 = f32[8,16]{1,0} reduce-scatter(f32[8,256]{1,0} %p2), channel_id=3, replica_groups=[1,16]<=[16], dimensions={1}
  %collective-permute.4 = bf16[4,4]{1,0} collective-permute(bf16[4,4]{1,0} %p3), channel_id=4, source_target_pairs={{0,1}}
  %fusion.9 = f32[2,2]{1,0} fusion(f32[2,2]{1,0} %p4), kind=kLoop
"""


def test_parse_collectives_kinds_and_sizes():
    ops = parse_collectives(HLO_SAMPLE)
    kinds = sorted(o.kind for o in ops)
    assert kinds == ["all-gather", "all-reduce", "collective-permute", "reduce-scatter"]
    ag = next(o for o in ops if o.kind == "all-gather")
    assert ag.group_size == 16
    assert ag.result_bytes == 16 * 512 * 2
    assert ag.operand_bytes == ag.result_bytes // 16
    ar = next(o for o in ops if o.kind == "all-reduce")
    assert ar.group_size == 4 and ar.operand_bytes == 128 * 64 * 4
    rs = next(o for o in ops if o.kind == "reduce-scatter")
    assert rs.group_size == 16 and rs.operand_bytes == 8 * 16 * 4 * 16


def test_ring_model_bytes():
    op = CollectiveOp("all-reduce", "f32", 1000, 1000, 4)
    assert op.ring_link_bytes == pytest.approx(2 * 1000 * 3 / 4)
    op = CollectiveOp("all-gather", "f32", 4000, 1000, 4)
    assert op.ring_link_bytes == pytest.approx(3000)
    assert CollectiveOp("all-reduce", "f32", 10, 10, 1).ring_link_bytes == 0.0


def test_summarize_collectives():
    s = summarize_collectives(parse_collectives(HLO_SAMPLE))
    assert s["n_ops"] == 4
    assert s["operand_bytes"] > 0 and s["ring_link_bytes"] > 0


# ------------------------------------------------------------------ cell specs
def test_cell_grid_counts():
    """10 archs x 4 shapes with the documented long_500k skips = 32 runnable
    cells; every skip is a pure full-attention arch."""
    runnable = sum(len(cells_for(get_config(a))) for a in ARCH_IDS)
    assert runnable == 32
    for a in ARCH_IDS:
        cfg = get_config(a)
        ok, reason = applicable(cfg, "long_500k")
        if not ok:
            assert cfg.family not in ("ssm", "hybrid")
            assert "full-attention" in reason


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_train_batch_specs_match_shape(arch):
    cfg = get_config(arch)
    shape = SHAPES["train_4k"]
    specs = train_batch_specs(cfg, shape)
    total = shape.seq_len
    text = total - (cfg.prefix_len or 0)
    assert specs["labels"].shape == (shape.global_batch, text)
    if cfg.train_input == "embeds":
        assert specs["embeds"].shape == (shape.global_batch, text, cfg.d_model)
    if cfg.prefix_len:
        assert specs["prefix_embeds"].shape[1] == cfg.prefix_len


def test_model_flops_scaling():
    cfg = get_config("llama3-8b")
    train = model_flops(cfg, SHAPES["train_4k"])
    prefill = model_flops(cfg, SHAPES["prefill_32k"])
    decode = model_flops(cfg, SHAPES["decode_32k"])
    # same token count -> train is 3x prefill (fwd+bwd vs fwd)
    assert train / prefill == pytest.approx(3.0)
    # decode computes one token per sequence
    assert decode == pytest.approx(prefill * 128 / (32 * 32768))
    # magnitude: 6 * ~7.5B * 1M tokens
    assert 3e16 < train < 8e16
