"""The Qwen2 configuration file's sizes and the benchmark's weights, in
the form the program under test takes (``repro.models.config.ModelConfig``
and the parameter tree of ``repro.models.transformer.init_lm``)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.models import qwen2 as ref
from repro.models.config import CCMConfig, ModelConfig


def model_config(cfg: dict) -> ModelConfig:
    m = ref.dims(cfg)
    return ModelConfig(
        name=cfg["name"], family="dense", n_layers=m.n_layers, d_model=m.d,
        n_heads=m.n_heads, n_kv_heads=m.n_kv, d_ff=m.f,
        vocab_size=m.vocab, activation="swiglu", norm="rms",
        qkv_bias=True, rope_theta=m.theta, norm_eps=m.eps,
        tie_embeddings=m.tied,
        param_dtype=cfg.get("param_dtype", "bfloat16"),
        compute_dtype=cfg.get("compute_dtype", "bfloat16"),
        ccm=CCMConfig(comp_len=m.comp_len, max_steps=m.max_steps,
                      lora_rank=m.rank, lora_alpha=m.alpha))


def to_program(m: ref.Dims, w: dict, dtype) -> dict:
    """Reference layout -> program layout: projections (out, in) ->
    (in, out), RMSNorm weight w -> stored scale w - 1, LoRA B (out, r) ->
    (r, out)."""
    t = lambda a: jnp.swapaxes(a, -1, -2).astype(dtype)       # noqa: E731
    scale = lambda a: {"scale": (a.astype(jnp.float32) - 1.0  # noqa: E731
                                 ).astype(dtype)}
    lora = {n: {"a": w[f"{n}_A"].astype(dtype), "b": t(w[f"{n}_B"])}
            for n in "qkvo"}
    p = {"embed": w["embed"].astype(dtype),
         "final_norm": scale(w["final_norm"]),
         "comp_embed": w["comp_embed"].astype(dtype),
         "layers": {
             "ln1": scale(w["ln1"]), "ln2": scale(w["ln2"]),
             "attn": {"wq": t(w["q_w"]), "wk": t(w["k_w"]),
                      "wv": t(w["v_w"]), "wo": t(w["o_w"]),
                      "bq": w["q_b"].astype(dtype),
                      "bk": w["k_b"].astype(dtype),
                      "bv": w["v_b"].astype(dtype), "lora": lora},
             "mlp": {"wi": t(w["up_w"]), "wg": t(w["gate_w"]),
                     "wo": t(w["down_w"])}}}
    if not m.tied:
        p["lm_head"] = t(w["lm_head"])
    return p


def program_params(cfg: dict, mcfg: ModelConfig, seed: int) -> dict:
    """The program's parameter tree, made on the device from ``seed`` in
    one jitted call (the same values as ``ref.init_weights(seed)``)."""
    m = ref.dims(cfg)
    dtype = jnp.dtype(mcfg.param_dtype)
    make = jax.jit(lambda key: to_program(m, ref.make_weights(m, key),
                                          dtype))
    return make(ref.seed_key(seed))
