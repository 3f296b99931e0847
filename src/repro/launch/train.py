"""Training: jitted CCM train step (pjit/GSPMD) + fault-tolerant loop.

``make_train_step`` builds one XLA program containing: CCM parallelized
forward (paper Alg. 1), masked tail loss, backprop restricted to the
trainable partition (LoRA-only by default — the paper's regime), optional
gradient compression on the DP reduce (shard_map over the data/pod axes,
model axis left to GSPMD), AdamW update.

``TrainLoop`` adds production concerns: checkpoint/restart (atomic + async),
elastic restore onto a different mesh, step-time watchdog (straggler
detection), deterministic restartable data order.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.checkpoint.manager import CheckpointManager
from repro.core import masks as M
from repro.data.synthetic import ShardableIndexIterator, sample_kv_batch
from repro.distributed import sharding as SH
from repro.distributed.context import DistContext
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.obs import perf_counter
from repro.optim import partition as PT
from repro.optim.adamw import AdamWConfig, AdamWState, adamw_update, init_adamw
from repro.optim.grad_compress import EFState, compressed_psum, init_ef
from repro.optim.losses import next_token_loss


def trainable_mask_for(cfg: ModelConfig, params_shapes) -> Any:
    if cfg.train_mode == "lora":
        return PT.trainable_mask(params_shapes, PT.lora_predicate)
    return jax.tree.map(lambda _: True, params_shapes)


def _loss_fn(tp, fp, cfg: ModelConfig, layout: M.SegmentLayout, batch,
             dist: Optional[DistContext]):
    params = PT.merge(tp, fp)
    kw = {}
    if cfg.family == "encdec":
        kw["frames"] = batch["frames"]
    if cfg.family == "vlm":
        kw["patches"] = batch["patches"]
    logits = T.train_forward(params, cfg, batch["tokens"], layout,
                             dist=dist, **kw)
    tail = batch["tokens"][:, layout.seq_len - layout.tail_len:]
    return next_token_loss(logits, tail, batch["loss_mask"])


def make_train_step(cfg: ModelConfig, layout: M.SegmentLayout,
                    opt_cfg: AdamWConfig,
                    dist: Optional[DistContext] = None,
                    grad_codec: str = "none",
                    topk_frac: float = 0.01) -> Callable:
    """Returns step(train_params, frozen_params, opt_state, batch, ef)
    -> (train_params, opt_state, metrics, ef)."""

    def step(tp, fp, opt: AdamWState, batch, ef: Optional[EFState]):
        if grad_codec != "none" and dist is not None:
            # grads per data shard -> codec + psum over data/pod (wire),
            # model axis left automatic (TP sharding preserved). EF residual
            # is PER-SHARD state: leading device axis, sharded on data.
            def shard_grads(tpp, fpp, bb, eff):
                loss, grads = jax.value_and_grad(_loss_fn)(
                    tpp, fpp, cfg, layout, bb, dist)
                eff_local = jax.tree.map(lambda r: r[0], eff.residual)
                grads, new_ef = compressed_psum(
                    grads, dist.batch_axes, EFState(eff_local),
                    grad_codec, topk_frac)
                loss = jax.lax.pmean(loss, dist.batch_axes)
                new_ef = EFState(jax.tree.map(lambda r: r[None],
                                              new_ef.residual))
                return loss, grads, new_ef

            nb = dist.n_data
            ef_spec = EFState(jax.tree.map(
                lambda _: P(dist.batch_axes), ef.residual))
            loss, grads, ef = jax.shard_map(
                shard_grads, mesh=dist.mesh,
                in_specs=(P(), P(), SH.batch_spec(dist), ef_spec),
                out_specs=(P(), P(), ef_spec),
                axis_names=set(dist.batch_axes),
                check_vma=False)(tp, fp, batch, ef)
            grads = jax.tree.map(lambda g: g / nb, grads)
        else:
            loss, grads = jax.value_and_grad(_loss_fn)(
                tp, fp, cfg, layout, batch, dist)
        mask = jax.tree.map(lambda _: True, tp)
        new_tp, new_opt, metrics = adamw_update(opt_cfg, tp, grads, opt, mask)
        metrics["loss"] = loss
        return new_tp, new_opt, metrics, ef

    return step


def jit_train_step(step_fn, cfg: ModelConfig, dist: DistContext,
                   params_shapes, opt_shapes, batch_shapes,
                   trainable) -> Callable:
    """pjit with explicit in/out shardings derived from the rules."""
    pspecs = SH.param_pspecs(cfg, params_shapes, dist)
    tp_specs, fp_specs = PT.partition(pspecs, trainable)
    opt_specs = SH.opt_pspecs(tp_specs, opt_shapes)
    bspecs = {k: SH.batch_spec(dist, extra_dims=len(v.shape) - 1)
              for k, v in batch_shapes.items()}
    mesh = dist.mesh
    in_sh = (SH.named(mesh, tp_specs), SH.named(mesh, fp_specs),
             SH.named(mesh, opt_specs), SH.named(mesh, bspecs), None)
    out_sh = (SH.named(mesh, tp_specs), SH.named(mesh, opt_specs),
              None, None)
    return jax.jit(step_fn, in_shardings=in_sh, out_shardings=out_sh,
                   donate_argnums=(0, 2))


# ===========================================================================
# fault-tolerant loop
# ===========================================================================

@dataclasses.dataclass
class WatchdogStats:
    """Step-time watchdog: flags straggling steps (>k x median)."""
    times: list = dataclasses.field(default_factory=list)
    threshold: float = 3.0

    def record(self, dt: float) -> bool:
        self.times.append(dt)
        if len(self.times) < 5:
            return False
        med = sorted(self.times[-50:])[len(self.times[-50:]) // 2]
        return dt > self.threshold * med


class TrainLoop:
    """Checkpointed, restartable training driver (single-host harness for
    the multi-host pattern; data order and checkpoint layout are host-count
    independent)."""

    def __init__(self, cfg: ModelConfig, layout: M.SegmentLayout,
                 opt_cfg: AdamWConfig, batch_size: int,
                 ckpt_dir: Optional[str] = None, seed: int = 0,
                 dist: Optional[DistContext] = None,
                 ckpt_every: int = 50, grad_codec: str = "none"):
        self.cfg, self.layout, self.opt_cfg = cfg, layout, opt_cfg
        self.batch_size = batch_size
        self.dist = dist
        params = T.init_lm(jax.random.PRNGKey(seed), cfg)
        self.trainable = trainable_mask_for(cfg, params)
        self.tp, self.fp = PT.partition(params, self.trainable)
        self.opt = init_adamw(self.tp)
        if grad_codec == "none":
            self.ef = None
        elif dist is not None:
            self.ef = EFState(jax.tree.map(
                lambda p: jnp.zeros((dist.n_data,) + p.shape, jnp.float32),
                self.tp))
        else:
            self.ef = init_ef(self.tp)
        self.it = ShardableIndexIterator(seed, batch_size)
        step_fn = make_train_step(cfg, layout, opt_cfg, dist, grad_codec)
        self.step_fn = jax.jit(step_fn) if dist is None else step_fn
        self.ckpt = CheckpointManager(ckpt_dir) if ckpt_dir else None
        self.ckpt_every = ckpt_every
        self.watchdog = WatchdogStats()
        self.history: list = []

    # ------------------------------------------------------------------
    def maybe_restore(self):
        if self.ckpt is None:
            return 0
        latest = self.ckpt.latest()
        if latest is None:
            return 0
        state_tmpl = {"tp": self.tp, "opt": self.opt}
        restored, extra = self.ckpt.restore(latest, state_tmpl)
        self.tp, self.opt = restored["tp"], restored["opt"]
        self.it.load_state_dict(extra["iterator"])
        return int(extra["step"])

    def run(self, n_steps: int, start_step: int = 0,
            log_every: int = 10) -> list:
        for s in range(start_step, n_steps):
            key = self.it.next_key()
            batch = sample_kv_batch(key, self.layout, self.batch_size)
            t0 = perf_counter()
            self.tp, self.opt, metrics, self.ef = self.step_fn(
                self.tp, self.fp, self.opt, batch, self.ef)
            loss = float(metrics["loss"])
            dt = perf_counter() - t0
            straggle = self.watchdog.record(dt)
            self.history.append({"step": s, "loss": loss, "dt": dt,
                                 "straggler": straggle})
            if log_every and s % log_every == 0:
                print(f"step {s:5d} loss {loss:.4f} "
                      f"dt {dt*1e3:7.1f}ms{'  STRAGGLER' if straggle else ''}")
            if self.ckpt and (s + 1) % self.ckpt_every == 0:
                self.ckpt.save(s + 1, {"tp": self.tp, "opt": self.opt},
                               extra={"step": s + 1,
                                      "iterator": self.it.state_dict()})
        if self.ckpt:
            self.ckpt.wait()
        return self.history
