"""Distribution layer units: sharding policy rules, input specs, and the
HLO collective parser (no SPMD compilation needed)."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import get_config
from repro.launch import specs
from repro.launch.dryrun import _result_bytes, collective_bytes
from repro.launch.mesh import make_host_mesh
from repro.launch.sharding import (ShardingOptions, batch_shardings,
                                   cache_shardings, param_spec)

pytestmark = pytest.mark.filterwarnings("ignore")


class FakeMesh:
    axis_names = ("data", "model")
    shape = {"data": 16, "model": 16}


MESH = FakeMesh()


def test_param_spec_baseline_rules():
    cfg = get_config("qwen1.5-110b")      # fsdp=True
    # attention qkv: (d, heads*hd) -> (fsdp, model)
    assert param_spec(MESH, cfg, "units/slot0/attn/wq", (80, 8192, 8192)) \
        == P(None, "data", "model")
    # output proj flips
    assert param_spec(MESH, cfg, "units/slot0/attn/wo", (80, 8192, 8192)) \
        == P(None, "model", "data")
    # embed: vocab on model
    assert param_spec(MESH, cfg, "embed", (152064, 8192)) == P("model", "data")
    # norms replicated beyond the stack axis
    assert param_spec(MESH, cfg, "units/slot0/norm1", (80, 8192)) == P(None, None)


def test_param_spec_non_divisible_replicates():
    cfg = get_config("gemma3-1b")         # 4 heads * 256 = 1024 cols; d=1152
    # Head-granular TP: gemma3's 4 query heads (and 1 kv head) cannot split
    # over a 16-way model axis, so the projections replicate even though
    # their flat column counts (1024, 256) divide 16 -- a mid-head split
    # breaks per-head ops (RoPE, qk-norm, GQA grouping).
    spec = param_spec(MESH, cfg, "units/slot0/attn/wq", (4, 1152, 1024))
    assert spec == P(None, None, None)
    spec = param_spec(MESH, cfg, "units/slot0/attn/wk", (4, 1152, 256))
    assert spec == P(None, None, None)
    assert param_spec(MESH, cfg, "units/slot0/attn/wo", (4, 1024, 1152)) \
        == P(None, None, None)
    # head-aligned counts DO shard: qwen1.5's 8 kv heads on 8-way would,
    # but on this 16-way mesh 8 % 16 != 0 -> replicated too
    cfg_q = get_config("qwen1.5-110b")
    assert param_spec(MESH, cfg_q, "units/slot0/attn/wk", (80, 8192, 1024)) \
        == P(None, "data", None)
    # d_model 1152 not divisible by 16 on the fsdp side (fsdp=False anyway)
    assert param_spec(MESH, cfg, "final_norm", (1152,)) == P(None)


def test_param_spec_moe_rules():
    cfg = get_config("llama4-maverick-400b-a17b")
    base = param_spec(MESH, cfg, "units/slot1/moe/wi", (24, 128, 5120, 8192))
    assert base == P(None, "model", "data", None)      # EP + FSDP-D
    dff = param_spec(MESH, cfg, "units/slot1/moe/wi", (24, 128, 5120, 8192),
                     ShardingOptions(expert_shard_dff=True))
    assert dff == P(None, "model", None, "data")       # resident, F over data
    epd = param_spec(MESH, cfg, "units/slot1/moe/wi", (24, 128, 5120, 8192),
                     ShardingOptions(expert_mesh="data"))
    assert epd == P(None, "data", None, "model")


def test_param_spec_tp_modes():
    cfg = get_config("qwen3-0.6b")
    full = param_spec(MESH, cfg, "units/slot0/ffn/w1", (28, 1024, 3072))
    assert full == P(None, None, "model")
    vocab_only = param_spec(MESH, cfg, "units/slot0/ffn/w1", (28, 1024, 3072),
                            ShardingOptions(tp_mode="vocab-only"))
    assert vocab_only == P(None, None, None)
    # vocab sharding survives
    assert param_spec(MESH, cfg, "embed", (151936, 1024),
                      ShardingOptions(tp_mode="vocab-only"))[0] == "model"


def test_param_spec_zero2d_without_tp():
    cfg = get_config("qwen1.5-110b")
    opts = ShardingOptions(tp_mode="vocab-only")
    spec = param_spec(MESH, cfg, "units/slot0/ffn/w1", (80, 8192, 49152), opts)
    assert spec == P(None, ("data", "model"), None)    # 256-way storage


def test_input_specs_shapes():
    cfg = get_config("qwen3-0.6b")
    train = specs.input_specs(cfg, "train_4k")
    assert train["batch"]["tokens"].shape == (256, 4096)
    assert train["batch"]["targets"].dtype == jnp.int32
    dec = specs.input_specs(cfg, "decode_32k")
    assert dec["tokens"].shape == (128,)
    # cache via eval_shape: stacked KV (units, B, s_max, kv, hd)
    kv = dec["cache"]["units"]["slot0"].k
    assert kv.shape == (28, 128, 32768 + specs.DECODE_MARGIN, 8, 128)


def test_input_specs_modalities():
    vlm = get_config("llama-3.2-vision-90b")
    b = specs.input_specs(vlm, "train_4k")["batch"]
    assert b["image_embeds"].shape == (256, 1024, 8192)
    audio = get_config("seamless-m4t-large-v2")
    b = specs.input_specs(audio, "prefill_32k")["batch"]
    assert b["src_embeds"].shape == (32, 32768, 1024)
    assert b["tokens"].shape == (32, 32768 // 4)


def test_cell_supported_skip_rules():
    ok, _ = specs.cell_supported(get_config("mamba2-1.3b"),
                                 specs.SHAPES["long_500k"])
    assert ok
    ok, reason = specs.cell_supported(get_config("qwen1.5-110b"),
                                      specs.SHAPES["long_500k"])
    assert not ok and "sub-quadratic" in reason
    ok, _ = specs.cell_supported(get_config("gemma3-1b"),
                                 specs.SHAPES["long_500k"])
    assert ok  # 5:1 local:global qualifies


# ---------------------------------------------------------------------------
# batch / cache sharding maps (need a real mesh for NamedSharding)
# ---------------------------------------------------------------------------

def _real_mesh():
    return make_host_mesh()


def test_batch_shardings_scalar_leaf_replicated():
    """Regression: 0-d leaves used to raise IndexError on shape[0]."""
    mesh = _real_mesh()
    cfg = get_config("qwen3-0.6b")
    tree = {"tokens": jax.ShapeDtypeStruct((8, 16), jnp.int32),
            "step": jax.ShapeDtypeStruct((), jnp.int32)}
    sh = batch_shardings(mesh, cfg, tree)
    assert sh["step"].spec == P()
    assert sh["tokens"].spec == P(("data",), None)


def test_cache_shardings_batch_position_rules():
    """Batch-dim matching is restricted to the layout's positions: leading
    for tail leaves (B, ...), second for stacked leaves (units, B, ...).  A
    dim that merely coincides with B elsewhere stays replicated (regression:
    the old fallback sharded ANY dim equal to batch)."""
    mesh = _real_mesh()
    cfg = get_config("qwen3-0.6b")
    B = 4
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    cache = {
        "units": {"slot0": {"k": f32(2, B, 8, 2, 4),
                            "v": f32(2, B, 8, 2, 4),
                            "state": f32(2, B, 16)}},
        "tail": [{"conv": f32(B, 3, 16)}],
        "pos": jax.ShapeDtypeStruct((), jnp.int32),
        "coincidence": f32(3, 5, B),
    }
    sh = cache_shardings(mesh, cfg, cache, batch=B)
    # KV tensors keep the dedicated (lead, batch, seq, kv, hd) rule
    assert P(sh["units"]["slot0"]["k"].spec[1]) == P(("data",))
    # stacked recurrent state: batch at dim 1
    assert sh["units"]["slot0"]["state"].spec == P(None, ("data",), None)
    # tail leaf: batch leading
    assert sh["tail"][0]["conv"].spec == P(("data",), None, None)
    # scalars and coincidental matches: replicated
    assert sh["pos"].spec == P()
    assert sh["coincidence"].spec == P()


# ---------------------------------------------------------------------------
# HLO collective parser
# ---------------------------------------------------------------------------

_FAKE_HLO = """
HloModule jit_step

%region_inner.1 (arg.1: (s32[], f32[16,64])) -> (s32[], f32[16,64]) {
  %ar = f32[16,64]{1,0} all-reduce(%x), replica_groups={}
  ROOT %t = (s32[], f32[16,64]) tuple(%i, %ar)
}

%region_outer.2 (arg.2: (s32[], f32[16,64])) -> (s32[], f32[16,64]) {
  %w = (s32[], f32[16,64]) while(%arg.2), condition=%cond.9, body=%region_inner.1
  %ag = bf16[32,64]{1,0} all-gather(%y), channel_id=1
  ROOT %t2 = (s32[], f32[16,64]) tuple(%i2, %gte)
}

%cond.9 (arg.3: (s32[], f32[16,64])) -> pred[] {
  ROOT %lt = pred[] compare(%a, %b), direction=LT
}

ENTRY %main.4 (p0: f32[16,64]) -> f32[16,64] {
  %w2 = (s32[], f32[16,64]) while(%init), condition=%cond.9, body=%region_outer.2
  %ar2 = f32[8,8]{1,0} all-reduce(%z), replica_groups={}
  ROOT %out = f32[16,64] get-tuple-element(%w2), index=1
}
"""


def test_collective_parser_nested_trips():
    out = collective_bytes(_FAKE_HLO, loop_trips=[3, 5])
    # inner AR: 16*64*4 bytes x (3 outer x 5 inner) = 61440
    # entry AR: 8*8*4 = 256 (x1)
    assert out["bytes_by_kind"]["all-reduce"] == 16 * 64 * 4 * 15 + 256
    # AG at depth 1: 32*64*2 x 3
    assert out["bytes_by_kind"]["all-gather"] == 32 * 64 * 2 * 3
    # f32 split: everything except the bf16 AG
    assert out["f32_bytes"] == 16 * 64 * 4 * 15 + 256
    corrected = out["bf16_wire_corrected_bytes"]
    assert corrected == out["total_bytes"] - 0.5 * out["f32_bytes"]


def test_result_bytes_tuples_and_scalars():
    assert _result_bytes("%x = f32[4,4]{1,0} add(%a, %b)") == 64
    assert _result_bytes(
        "%t = (f32[2,2]{1,0}, bf16[4]{0}) all-reduce(%a, %b)") == 16 + 8
    assert _result_bytes("ROOT %r = pred[] compare(%a, %b)") == 1
