"""The FLOP and byte counts behind ``step_mfu`` and ``arena_gs_roofline``,
against values worked out by hand at each configuration's shapes."""
import json
import os

import pytest

from bench import counts
from bench.models import qwen2

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def dims(name, **over):
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        cfg = json.load(f)
    cfg.update(over)
    return qwen2.dims(cfg)


def test_qwen2_parameter_count():
    m = dims("qwen2-0.5b")
    # per layer: q 896*896 + k,v 2*896*128 + o 896*896 + MLP 3*896*4864
    assert counts.layer_matmul_params(m) == 14_909_440
    # + q/k/v biases 1152 + two norms 1792, x24; tied embedding 151936*896;
    # final norm 896
    assert counts.param_count(m) == 494_032_768


@pytest.mark.parametrize("layers,expected", [(32, 7_250_284_544),
                                             (8, 2_380_378_112)])
def test_codeqwen_parameter_count(layers, expected):
    m = dims("codeqwen1.5-7b-8l", num_hidden_layers=layers)
    # per layer 4096^2*2 + 2*4096*512 + 3*4096*13440 = 202,899,456 matmul
    # weights, + 5120 biases + 8192 norm weights; untied 2*92416*4096
    assert counts.layer_matmul_params(m) == 202_899_456
    assert counts.param_count(m) == expected
    if layers == 32:
        assert abs(counts.param_count(m) / 7.25e9 - 1) < 1e-3
        # the registry's 32 KV heads would make it an 8.19B model
        m32 = dims("codeqwen1.5-7b-8l", num_hidden_layers=32,
                   num_key_value_heads=32)
        assert abs(counts.param_count(m32) / 8.19e9 - 1) < 1e-3


def test_qwen2_query_flops():
    m = dims("qwen2-0.5b")
    # 16 tokens after 8 memory groups (64 tokens) and 32 cached tokens:
    # dense 2*(14,909,440*24 + 896*151,936)*16, attention
    # 4*14*64*(16*96 + 16*17/2)*24
    assert counts.query_flops(m, 16, 64, 32) == \
        15_806_758_912 + 143_818_752


def test_qwen2_ingest_flops():
    m = dims("qwen2-0.5b")
    # a 100-token chunk + 8 <COMP> tokens on an empty memory and cache:
    # dense 2*14,909,440*24*108; attention 4*14*64*(108*109/2)*24;
    # LoRA at 8 <COMP> tokens: 2*8*((896+896) + 2*(896+128) + (896+896))*24
    dense = 2 * 14_909_440 * 24 * 108
    attn = 3584 * 5886 * 24
    lora = 8 * 2 * 8 * (1792 + 2048 + 1792) * 24
    assert counts.ingest_flops(m, 100, 0, 0) == dense + attn + lora


def test_codeqwen_query_flops():
    m = dims("codeqwen1.5-7b-8l")
    # 8 layers of 202,899,456 weights + the untied head 4096*92416
    dense = 2 * (202_899_456 * 8 + 4096 * 92416) * 10
    attn = 4 * 32 * 128 * (10 * 40 + 55) * 8
    assert counts.query_flops(m, 10, 24, 16) == dense + attn


def test_qwen2_arena_bytes():
    m = dims("qwen2-0.5b")
    rb = counts.row_bytes(m, 256)
    # 24 layers * 2 KV heads * 64 * bf16 = 6144 bytes per token for k or v
    assert rb["mem_kv"] == 2 * 6144 * 128
    assert rb["cache_kv"] == 2 * 6144 * 256
    assert sum(rb.values()) == 4_718_612
    # query, 16 lanes: gather 2*16*row, scatter 2*16*(cache kv + counters)
    assert counts.gather_scatter_bytes(m, 256, "query", 16) == \
        2 * 16 * 4_718_612 + 2 * 16 * (3_145_728 + 8)
    assert counts.gather_scatter_bytes(m, 256, "ingest", 4) == \
        2 * 4 * 4_718_612 + 2 * 4 * (1_572_864 + 16)


def test_codeqwen_arena_row():
    m = dims("codeqwen1.5-7b-8l")
    # 8 layers * 4 KV heads * 128 * bf16 = 8192 bytes per token for k or v
    assert sum(counts.row_bytes(m, 256).values()) == \
        2 * 8192 * (256 + 128) + 20
