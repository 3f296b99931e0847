"""Operations and bytes the algorithm needs, computed from shapes alone.

``step_mfu`` counts model FLOPs per real token of a fused step:
2 x every matmul weight the token passes through (the layers' q/k/v/o
and MLP projections, the LM head at the configuration's vocabulary for
query tokens; never the embedding lookup), the conditional LoRA's
2 x r x (in + out) per projection at <COMP> tokens, and attention:
4 x heads x head_dim x the keys the token attends (memory + cached query
tokens + its causal prefix).  Pad tokens and pad lanes count nothing.

``arena_gs_roofline`` counts the bytes the arena gather and scatter
need: a gather reads B rows of every slab leaf and writes them to the
batch, a scatter reads B rows of every leaf the op writes and writes
them to the slab.  B is the batch's lane count.
"""
from __future__ import annotations

from typing import Dict, List

BF16 = 2
I32 = 4


def layer_matmul_params(m) -> int:
    """Weights of one layer's projections (q, k, v, o, gate, up, down)."""
    q, kv = m.n_heads * m.hd, m.n_kv * m.hd
    return m.d * q + 2 * m.d * kv + q * m.d + 3 * m.d * m.f


def param_count(m) -> int:
    """Every weight of the published model (biases and norms included,
    the CCM embeddings and LoRA left out)."""
    q, kv = m.n_heads * m.hd, m.n_kv * m.hd
    per_layer = layer_matmul_params(m) + q + 2 * kv + 2 * m.d
    emb = m.vocab * m.d * (1 if m.tied else 2)
    return m.n_layers * per_layer + emb + m.d


def lora_flops_per_comp_token(m) -> int:
    q, kv = m.n_heads * m.hd, m.n_kv * m.hd
    io = (m.d + q) + 2 * (m.d + kv) + (q + m.d)
    return 2 * m.rank * io * m.n_layers


def attn_flops(m, keys: int) -> int:
    return 4 * m.n_heads * m.hd * keys * m.n_layers


def ingest_flops(m, n: int, mem_tokens: int, cached: int) -> int:
    """A chunk of n real tokens plus comp_len <COMP> tokens."""
    toks = n + m.comp_len
    dense = 2 * layer_matmul_params(m) * m.n_layers * toks
    keys = toks * (mem_tokens + cached) + toks * (toks + 1) // 2
    return dense + attn_flops(m, keys) + m.comp_len \
        * lora_flops_per_comp_token(m)


def query_flops(m, n: int, mem_tokens: int, cached: int) -> int:
    dense = 2 * (layer_matmul_params(m) * m.n_layers + m.d * m.vocab) * n
    keys = n * (mem_tokens + cached) + n * (n + 1) // 2
    return dense + attn_flops(m, keys)


def row_bytes(m, cache_len: int) -> Dict[str, int]:
    """Bytes of one session's arena row per leaf group: the memory's and
    the cache's keys and values (bfloat16) and the int32 counters."""
    per_tok = m.n_layers * m.n_kv * m.hd * BF16
    return {"mem_kv": 2 * per_tok * m.max_steps * m.comp_len,
            "cache_kv": 2 * per_tok * cache_len,
            "mem_counters": 3 * I32, "cache_counters": I32, "pos": I32}


WRITES = {"ingest": ("mem_kv", "mem_counters", "pos"),
          "query": ("cache_kv", "cache_counters", "pos")}


def gather_scatter_bytes(m, cache_len: int, op: str, lanes: int) -> int:
    rb = row_bytes(m, cache_len)
    gather = 2 * lanes * sum(rb.values())
    scatter = 2 * lanes * sum(rb[k] for k in WRITES[op])
    return gather + scatter


def steps_bytes(m, cache_len: int, steps: List[tuple]) -> int:
    """Sum over fused steps given as (op, lanes)."""
    return sum(gather_scatter_bytes(m, cache_len, op, b) for op, b in steps)
