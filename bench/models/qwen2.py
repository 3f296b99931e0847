"""Plain reference for the Qwen2 family with the paper's compressed
context memory, and the benchmark's own seeded weights.

Nothing here imports the program under test.  The weights use the
published checkpoint layout (Hugging Face ``Qwen2ForCausalLM``: every
projection stored ``(out, in)``, RMSNorm weights multiply the normalised
input), plus the CCM parts: ``comp_len`` learned ``<COMP>`` embeddings
and a rank-r LoRA on q/k/v/o that fires only at ``<COMP>`` positions
(paper Eq. 4, ``scale = alpha / rank``).

Online semantics, one session:

* ``ingest(chunk)``: the chunk's n tokens at stream positions
  ``pos .. pos+n-1`` are followed by ``comp_len`` ``<COMP>`` tokens at
  ``pos+n ..``; every token attends the memory, the cached query tokens
  and the causal prefix of the block.  The ``<COMP>`` tokens' keys and
  values (after RoPE), layer by layer, become the memory's next group.
  ``pos`` advances by ``n + comp_len``; nothing enters the cache.
* ``query(tokens)``: the n tokens attend the memory, the cache and
  their causal prefix, give logits at every position, and are appended
  to the cache; ``pos`` advances by n.

Everything is float32 at ``Precision.HIGHEST``; ``matmul="fp8"`` is the
control: each matmul's operands rounded to float8_e4m3fn with one scale
per tensor.
"""
from __future__ import annotations

import functools
from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


class Dims(NamedTuple):
    d: int
    f: int
    n_layers: int
    n_heads: int
    n_kv: int
    hd: int
    vocab: int
    tied: bool
    eps: float
    theta: float
    comp_len: int
    max_steps: int
    rank: int
    alpha: float


def dims(cfg: dict) -> Dims:
    c = cfg["ccm"]
    return Dims(d=cfg["hidden_size"], f=cfg["intermediate_size"],
                n_layers=cfg["num_hidden_layers"],
                n_heads=cfg["num_attention_heads"],
                n_kv=cfg["num_key_value_heads"],
                hd=cfg["hidden_size"] // cfg["num_attention_heads"],
                vocab=cfg["vocab_size"],
                tied=bool(cfg["tie_word_embeddings"]),
                eps=float(cfg["rms_norm_eps"]),
                theta=float(cfg["rope_theta"]),
                comp_len=c["comp_len"], max_steps=c["max_steps"],
                rank=c["lora_rank"], alpha=float(c["lora_alpha"]))


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def weight_shapes(m: Dims) -> Dict[str, tuple]:
    L, d, f, r = m.n_layers, m.d, m.f, m.rank
    q, kv = m.n_heads * m.hd, m.n_kv * m.hd
    s = {"embed": (m.vocab, d), "final_norm": (d,),
         "comp_embed": (m.comp_len, d),
         "ln1": (L, d), "ln2": (L, d),
         "q_w": (L, q, d), "q_b": (L, q), "k_w": (L, kv, d), "k_b": (L, kv),
         "v_w": (L, kv, d), "v_b": (L, kv), "o_w": (L, d, q),
         "gate_w": (L, f, d), "up_w": (L, f, d), "down_w": (L, d, f),
         "q_A": (L, r, d), "q_B": (L, q, r), "k_A": (L, r, d),
         "k_B": (L, kv, r), "v_A": (L, r, d), "v_B": (L, kv, r),
         "o_A": (L, r, q), "o_B": (L, d, r)}
    if not m.tied:
        s["lm_head"] = (m.vocab, d)
    return s


def _init_one(key, name, shape, m: Dims):
    z = jax.random.normal(key, shape, F32)
    if name in ("ln1", "ln2", "final_norm"):
        w = 1.0 + 0.1 * z
    elif name.endswith("_b"):
        w = 0.05 * z
    elif name.endswith("_A"):
        w = z / np.sqrt(shape[-1])
    else:                       # projections, embeddings, LoRA B
        w = 0.02 * z
    return w.astype(jnp.bfloat16)


def make_weights(m: Dims, key) -> Dict[str, jnp.ndarray]:
    """Every weight from ``key``, in bfloat16 (the published checkpoints'
    ``torch_dtype``).  Traceable, so a caller can make and convert the
    weights on the device in one jitted call."""
    shapes = weight_shapes(m)
    keys = jax.random.split(key, len(shapes))
    return {n: _init_one(k, n, s, m)
            for k, (n, s) in zip(keys, sorted(shapes.items()))}


def init_weights(m: Dims, seed: int) -> Dict[str, jnp.ndarray]:
    return jax.jit(make_weights, static_argnums=0)(m, seed_key(seed))


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number up to 2**64 (the high and low
    32-bit halves are folded in one after the other)."""
    seed = int(seed) % (1 << 64)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / s).astype(FP8).astype(F32) * s


def _mm(a, b, fp8: bool):
    """a @ b in float32, or on fp8-rounded operands for the control."""
    a, b = a.astype(F32), b.astype(F32)
    if fp8:
        a, b = _fp8(a), _fp8(b)
    return jnp.matmul(a, b, precision=HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(F32)


def _rope(x, pos, theta):
    """x (S, H, D) rotated at integer positions pos (S,)."""
    D = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=F32) / D)
    ang = pos.astype(F32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    rot = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], -1)
    return x * cos + rot * sin


class State(NamedTuple):
    mem_k: jnp.ndarray     # (L, max_steps*comp_len, n_kv, hd)
    mem_v: jnp.ndarray
    mem_groups: jnp.ndarray
    cache_k: jnp.ndarray   # (L, cache_len, n_kv, hd)
    cache_v: jnp.ndarray
    cache_n: jnp.ndarray
    pos: jnp.ndarray


def empty_state(m: Dims, cache_len: int) -> State:
    mem = jnp.zeros((m.n_layers, m.max_steps * m.comp_len, m.n_kv, m.hd), F32)
    cache = jnp.zeros((m.n_layers, cache_len, m.n_kv, m.hd), F32)
    z = jnp.zeros((), jnp.int32)
    return State(mem, mem, z, cache, cache, z, z)


def _block(m: Dims, w, st: State, x, pos, valid, gate, fp8: bool):
    """The layer stack over one block of S tokens.

    x (S, d) float32 embeddings; pos (S,) stream positions; valid (S,)
    real tokens (the rest are padding); gate (S,) 1.0 at <COMP> tokens.
    Returns the final hidden states and each layer's keys and values."""
    S = x.shape[0]
    G = m.n_heads // m.n_kv
    sc = m.alpha / m.rank
    Mk = st.mem_k.shape[1]
    Ck = st.cache_k.shape[1]
    mem_ok = jnp.arange(Mk) < st.mem_groups * m.comp_len
    cache_ok = jnp.arange(Ck) < st.cache_n
    ar = jnp.arange(S)
    self_ok = (ar[None, :] <= ar[:, None]) & valid[None, :]
    mask = jnp.concatenate([jnp.broadcast_to(mem_ok, (S, Mk)),
                            jnp.broadcast_to(cache_ok, (S, Ck)),
                            self_ok], axis=1)

    def lin(h, W, b, A, B):
        y = _mm(h, W.T, fp8)
        if b is not None:
            y = y + b.astype(F32)
        lora = _mm(_mm(h, A.T, fp8), B.T, fp8) * sc
        return y + gate[:, None] * lora

    def layer(x, lw):
        h = _rms(x, lw["ln1"], m.eps)
        q = lin(h, lw["q_w"], lw["q_b"], lw["q_A"], lw["q_B"])
        k = lin(h, lw["k_w"], lw["k_b"], lw["k_A"], lw["k_B"])
        v = lin(h, lw["v_w"], lw["v_b"], lw["v_A"], lw["v_B"])
        q = _rope(q.reshape(S, m.n_heads, m.hd), pos, m.theta)
        k = _rope(k.reshape(S, m.n_kv, m.hd), pos, m.theta)
        v = v.reshape(S, m.n_kv, m.hd)
        keys = jnp.concatenate([lw["mem_k"], lw["cache_k"], k], 0)
        vals = jnp.concatenate([lw["mem_v"], lw["cache_v"], v], 0)
        outs = []
        for h_i in range(m.n_heads):
            kv = h_i // G
            s = _mm(q[:, h_i], keys[:, kv].T, fp8) / np.sqrt(m.hd)
            s = jnp.where(mask, s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            outs.append(_mm(p, vals[:, kv], fp8))
        o = jnp.concatenate(outs, -1)
        x = x + lin(o, lw["o_w"], None, lw["o_A"], lw["o_B"])
        h = _rms(x, lw["ln2"], m.eps)
        act = jax.nn.silu(_mm(h, lw["gate_w"].T, fp8)) \
            * _mm(h, lw["up_w"].T, fp8)
        x = x + _mm(act, lw["down_w"].T, fp8)
        return x, (k, v)

    names = ("ln1", "ln2", "q_w", "q_b", "k_w", "k_b", "v_w", "v_b", "o_w",
             "gate_w", "up_w", "down_w", "q_A", "q_B", "k_A", "k_B", "v_A",
             "v_B", "o_A", "o_B")
    xs = {n: w[n] for n in names}
    xs.update(mem_k=st.mem_k, mem_v=st.mem_v, cache_k=st.cache_k,
              cache_v=st.cache_v)
    x, (ks, vs) = jax.lax.scan(layer, x, xs)
    return x, ks, vs


@functools.partial(jax.jit, static_argnames=("m", "fp8"))
def ingest(m: Dims, w, st: State, toks, n, fp8: bool = False) -> State:
    """Compress a chunk (``toks`` padded to a fixed length, ``n`` real)
    into the memory's next <COMP> group."""
    P, C = toks.shape[0], m.comp_len
    ar = jnp.arange(P + C)
    is_comp = ar >= P
    x = jnp.concatenate([w["embed"][toks].astype(F32),
                         w["comp_embed"].astype(F32)], 0)
    pos = st.pos + jnp.where(is_comp, n + ar - P, ar)
    valid = (ar < n) | is_comp
    _, ks, vs = _block(m, w, st, x, pos, valid, is_comp.astype(F32), fp8)
    at = st.mem_groups * C
    mem_k = jax.lax.dynamic_update_slice_in_dim(st.mem_k, ks[:, P:], at, 1)
    mem_v = jax.lax.dynamic_update_slice_in_dim(st.mem_v, vs[:, P:], at, 1)
    return st._replace(mem_k=mem_k, mem_v=mem_v,
                       mem_groups=st.mem_groups + 1, pos=st.pos + n + C)


@functools.partial(jax.jit, static_argnames=("m", "fp8"))
def query(m: Dims, w, st: State, toks, n, fp8: bool = False):
    """Logits (P, vocab) of a query (``toks`` padded, ``n`` real) and the
    state with its n tokens appended to the cache."""
    P = toks.shape[0]
    ar = jnp.arange(P)
    x = w["embed"][toks].astype(F32)
    x, ks, vs = _block(m, w, st, x, st.pos + ar, ar < n,
                       jnp.zeros((P,), F32), fp8)
    head = w["embed"] if m.tied else w["lm_head"]
    logits = _mm(_rms(x, w["final_norm"], m.eps), head.T, fp8)
    # cache rows [cache_n, cache_n + n) take this block's first n keys
    Ck = st.cache_k.shape[1]
    src = jnp.arange(Ck) - st.cache_n
    take = (src >= 0) & (src < n)
    idx = jnp.clip(src, 0, P - 1)

    def put(cache, new):
        return jnp.where(take[None, :, None, None], new[:, idx], cache)
    return logits, st._replace(cache_k=put(st.cache_k, ks),
                               cache_v=put(st.cache_v, vs),
                               cache_n=st.cache_n + n, pos=st.pos + n)
