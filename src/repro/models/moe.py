"""Mixture-of-Experts FFN: token-choice top-k routing, two SPMD strategies.

  ragged_tp — default. Token sort + ``jax.lax.ragged_dot`` grouped matmuls;
              expert weights are *tensor-parallel* (d_ff sharded on the model
              axis), tokens stay on their data shard, a single psum over the
              model axis combines partial outputs. No all_to_all; robust for
              any expert count (llama4's 128e and phi3.5's 16e).
  ep        — true expert parallelism. Experts are partitioned across the
              model axis; tokens are routed to expert owners with a
              capacity-bounded all_to_all inside shard_map (and back).
              Exercised in tests on a small mesh; selectable per config.

Router: softmax over expert logits (fp32), top-k, renormalized combine
weights (Mixtral convention). Dropless in ragged_tp; capacity-dropped in ep.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.context import DistContext
from repro.models.config import ModelConfig
from repro.models import layers as L


def init_moe(key, cfg: ModelConfig, d: int, f: int) -> Dict:
    E = cfg.n_experts
    ks = jax.random.split(key, 4)
    def ei(k, a, b):
        return (jax.random.normal(k, (E, a, b), jnp.float32) / jnp.sqrt(a)
                ).astype(cfg.pdtype)
    return {"router": L.dense_init(ks[0], d, E, jnp.float32),
            "wi": ei(ks[1], d, f), "wg": ei(ks[2], d, f), "wo": ei(ks[3], f, d)}


def _route(cfg: ModelConfig, router_w, xf):
    """xf (N, d) -> combine weights (N, k) fp32, expert ids (N, k) i32."""
    logits = (xf.astype(jnp.float32) @ router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, cfg.top_k)
    topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)
    return topw, topi.astype(jnp.int32)


def _expert_ffn(cfg: ModelConfig, p, xs, group_sizes):
    """Grouped (ragged) expert MLP. xs (M, d) sorted by expert."""
    wi = p["wi"].astype(xs.dtype)
    wg = p["wg"].astype(xs.dtype)
    wo = p["wo"].astype(xs.dtype)
    hg = jax.lax.ragged_dot(xs, wg, group_sizes)
    hi = jax.lax.ragged_dot(xs, wi, group_sizes)
    h = jax.nn.silu(hg) * hi
    return jax.lax.ragged_dot(h, wo, group_sizes)


def _moe_local(cfg: ModelConfig, p, xf):
    """Dropless sort-based MoE on one shard. xf (N, d) -> (N, d)."""
    N, d = xf.shape
    k, E = cfg.top_k, cfg.n_experts
    topw, topi = _route(cfg, p["router"], xf)
    eids = topi.reshape(-1)                                  # (N*k,)
    order = jnp.argsort(eids)                                # stable enough
    xr = jnp.repeat(xf, k, axis=0)[order]                    # (N*k, d)
    group_sizes = jnp.bincount(eids, length=E).astype(jnp.int32)
    y_sorted = _expert_ffn(cfg, p, xr, group_sizes)
    y = jnp.zeros_like(y_sorted).at[order].set(y_sorted)
    y = y.reshape(N, k, d) * topw[..., None].astype(y_sorted.dtype)
    return y.sum(axis=1)


# ---------------------------------------------------------------------------
# strategy: ragged_tp (shard_map over data x model; psum(model) combine)
# ---------------------------------------------------------------------------

def _moe_tp_shard(cfg: ModelConfig, p, xf, model_axis):
    """Per-shard body: experts' f-dim is local slice; combine via psum."""
    N, d = xf.shape
    k, E = cfg.top_k, cfg.n_experts
    topw, topi = _route(cfg, p["router"], xf)    # router fp32, replicated
    eids = topi.reshape(-1)
    order = jnp.argsort(eids)
    xr = jnp.repeat(xf, k, axis=0)[order]
    group_sizes = jnp.bincount(eids, length=E).astype(jnp.int32)
    wi = p["wi"].astype(xf.dtype)
    wg = p["wg"].astype(xf.dtype)
    wo = p["wo"].astype(xf.dtype)
    hg = jax.lax.ragged_dot(xr, wg, group_sizes)
    hi = jax.lax.ragged_dot(xr, wi, group_sizes)
    h = jax.nn.silu(hg) * hi                                  # local f-slice
    y_sorted = jax.lax.ragged_dot(h, wo, group_sizes)         # partial sum
    y_sorted = jax.lax.psum(y_sorted, model_axis)
    y = jnp.zeros_like(y_sorted).at[order].set(y_sorted)
    y = y.reshape(N, k, d) * topw[..., None].astype(y_sorted.dtype)
    return y.sum(axis=1)


# ---------------------------------------------------------------------------
# strategy: ep (expert parallel, capacity-bounded all_to_all)
# ---------------------------------------------------------------------------

def _moe_ep_shard(cfg: ModelConfig, p, xf, model_axis, ep: int,
                  capacity_factor: float = 1.25):
    """Per-shard body under shard_map: p['wi'] etc are (E/ep, d, f) local.

    Each shard routes its N local tokens, packs per-destination-shard
    buffers of fixed capacity C, all_to_all's them to expert owners,
    runs the local experts, and sends results back.
    """
    N, d = xf.shape
    k, E = cfg.top_k, cfg.n_experts
    e_local = E // ep
    C = int((N * k / ep) * capacity_factor) + 1
    topw, topi = _route(cfg, p["router"], xf)
    eids = topi.reshape(-1)                       # (N*k,)
    dest = eids // e_local                        # owner shard per assignment
    # position of each assignment within its destination buffer
    onehot = jax.nn.one_hot(dest, ep, dtype=jnp.int32)         # (N*k, ep)
    prior = jnp.cumsum(onehot, axis=0) - onehot
    pos_in_dest = jnp.take_along_axis(prior, dest[:, None], axis=1)[:, 0]
    pos = jnp.where(pos_in_dest < C, pos_in_dest, C)           # drop overflow
    xr = jnp.repeat(xf, k, axis=0)
    buf = jnp.zeros((ep, C + 1, d), xr.dtype)
    buf = buf.at[dest, pos].set(xr)                            # (ep, C+1, d)
    ebuf = jnp.full((ep, C + 1), e_local, jnp.int32)           # pad -> no-op id
    ebuf = ebuf.at[dest, pos].set(eids % e_local)
    buf = buf[:, :C]
    ebuf = ebuf[:, :C]
    # exchange: rows -> expert owners
    rbuf = jax.lax.all_to_all(buf, model_axis, 0, 0, tiled=False)   # (ep,C,d)
    rebuf = jax.lax.all_to_all(ebuf, model_axis, 0, 0, tiled=False)
    rx = rbuf.reshape(ep * C, d)
    re = rebuf.reshape(ep * C)
    order = jnp.argsort(re)
    gs = jnp.bincount(re, length=e_local + 1).astype(jnp.int32)
    pe = {kk: jnp.concatenate([p[kk], jnp.zeros_like(p[kk][:1])])
          for kk in ("wi", "wg", "wo")}                        # no-op expert
    ys = _expert_ffn(cfg, pe, rx[order], gs)
    y = jnp.zeros_like(ys).at[order].set(ys).reshape(ep, C, d)
    y = jax.lax.all_to_all(y, model_axis, 0, 0, tiled=False)   # back home
    out = y[dest, pos] * (pos_in_dest < C)[:, None].astype(y.dtype)
    out = out.reshape(N, k, d) * topw[..., None].astype(y.dtype)
    return out.sum(axis=1)


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------

def apply_moe(cfg: ModelConfig, p: Dict, x: jnp.ndarray,
              dist: Optional[DistContext] = None) -> jnp.ndarray:
    B, S, d = x.shape
    xf = x.reshape(B * S, d)
    if dist is None:
        return _moe_local(cfg, p, xf).reshape(B, S, d)

    ba, ma = dist.batch_axes, dist.model_axis
    if cfg.moe_impl == "ep":
        ep = dist.n_model
        body = functools.partial(_moe_ep_shard, cfg, model_axis=ma, ep=ep)
        y = jax.shard_map(
            lambda pp, xx: body(pp, xf=xx),
            mesh=dist.mesh,
            in_specs=({"router": P(), "wi": P(ma), "wg": P(ma), "wo": P(ma)},
                      P(ba)),
            out_specs=P(ba),
            check_vma=False,   # every model shard reproduces the combine
        )(p, xf)
    else:
        body = functools.partial(_moe_tp_shard, cfg, model_axis=ma)
        y = jax.shard_map(
            lambda pp, xx: body(pp, xx),
            mesh=dist.mesh,
            in_specs=({"router": P(), "wi": P(None, None, ma),
                       "wg": P(None, None, ma), "wo": P(None, ma, None)},
                      P(ba)),
            out_specs=P(ba),
        )(p, xf)
    return y.reshape(B, S, d)
