"""Multi-device tests (pjit shardings, MoE EP/TP, grad compression,
elastic restore). Each runs in a SUBPROCESS with
--xla_force_host_platform_device_count so the main pytest process keeps a
single device (assignment: never set the flag globally)."""
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(body: str, devices: int = 8, timeout: int = 900):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    prelude = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.models.config import ModelConfig, CCMConfig
        from repro.models import transformer as T
        from repro.core import masks as M
        from repro.launch.mesh import make_dist, make_debug_mesh
        from repro.launch.train import (make_train_step, jit_train_step,
                                        trainable_mask_for)
        from repro.optim import partition as PT
        from repro.optim.adamw import AdamWConfig, init_adamw
        from repro.data.synthetic import sample_kv_batch
    """)
    r = subprocess.run([sys.executable, "-c", prelude + textwrap.dedent(body)],
                       env=env, capture_output=True, text=True,
                       timeout=timeout)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_pjit_train_step_sharded():
    out = _run("""
        mesh = make_debug_mesh(2, 4)
        dist = make_dist(mesh)
        cfg = ModelConfig(name="d", family="dense", n_layers=2, d_model=64,
                          n_heads=8, n_kv_heads=4, d_ff=128, vocab_size=128,
                          train_mode="lora",
                          ccm=CCMConfig(comp_len=2, max_steps=4))
        layout = M.segment_layout(4, 8, 2, 8)
        params = T.init_lm(jax.random.PRNGKey(0), cfg)
        trainable = trainable_mask_for(cfg, params)
        tp, fp = PT.partition(params, trainable)
        opt = init_adamw(tp)
        batch = sample_kv_batch(jax.random.PRNGKey(1), layout, 8)
        step = make_train_step(cfg, layout, AdamWConfig(), dist)
        jstep = jit_train_step(step, cfg, dist, params,
                               jax.eval_shape(init_adamw, tp), batch,
                               trainable)
        tp2, opt2, m, _ = jstep(tp, fp, opt, batch, None)
        assert np.isfinite(float(m["loss"]))
        print("OK", float(m["loss"]))
    """)
    assert "OK" in out


def test_moe_tp_ep_equivalence():
    out = _run("""
        from repro.models import moe as MOE
        mesh = make_debug_mesh(2, 4)
        dist = make_dist(mesh)
        cfg = ModelConfig(name="m", family="moe", n_layers=1, d_model=64,
                          n_heads=4, n_kv_heads=4, d_ff=128, vocab_size=128,
                          n_experts=8, top_k=2, compute_dtype="float32",
                          ccm=CCMConfig(comp_len=2, max_steps=4))
        p = MOE.init_moe(jax.random.PRNGKey(0), cfg, 64, 128)
        x = jax.random.normal(jax.random.PRNGKey(5), (8, 16, 64))
        y_local = MOE._moe_local(cfg, p, x.reshape(-1, 64))
        y_tp = MOE.apply_moe(cfg.replace(moe_impl="ragged_tp"), p, x,
                             dist).reshape(-1, 64)
        y_ep = MOE.apply_moe(cfg.replace(moe_impl="ep"), p, x,
                             dist).reshape(-1, 64)
        for y in (y_tp, y_ep):
            assert float(jnp.abs(y - y_local).max()) < 1e-4
        print("OK")
    """)
    assert "OK" in out


def test_grad_compression_distributed():
    out = _run("""
        from repro.optim.grad_compress import EFState
        mesh = make_debug_mesh(2, 4)
        dist = make_dist(mesh)
        cfg = ModelConfig(name="d", family="dense", n_layers=2, d_model=64,
                          n_heads=8, n_kv_heads=4, d_ff=128, vocab_size=128,
                          train_mode="lora",
                          ccm=CCMConfig(comp_len=2, max_steps=4))
        layout = M.segment_layout(4, 8, 2, 8)
        params = T.init_lm(jax.random.PRNGKey(0), cfg)
        trainable = trainable_mask_for(cfg, params)
        tp, fp = PT.partition(params, trainable)
        opt = init_adamw(tp)
        batch = sample_kv_batch(jax.random.PRNGKey(1), layout, 8)
        ef = EFState(jax.tree.map(
            lambda p: jnp.zeros((2,) + p.shape, jnp.float32), tp))
        # int8-compressed step loss matches uncompressed step loss exactly
        # (loss is computed before the reduce)
        s_c = jax.jit(make_train_step(cfg, layout, AdamWConfig(), dist,
                                      grad_codec="int8"))
        s_u = jax.jit(make_train_step(cfg, layout, AdamWConfig(), dist))
        _, _, m_c, nef = s_c(tp, fp, opt, batch, ef)
        _, _, m_u, _ = s_u(tp, fp, opt, batch, None)
        # fp reduction-order noise between pmean-of-shard-means and
        # the global mean: tolerance is relative ~4e-4 at loss ~5.5
        assert abs(float(m_c["loss"]) - float(m_u["loss"])) < 2e-3
        resid = sum(float(jnp.abs(r).sum())
                    for r in jax.tree.leaves(nef.residual))
        assert np.isfinite(resid)
        print("OK")
    """)
    assert "OK" in out


def test_elastic_restore_new_mesh(tmp_path):
    out = _run(f"""
        from repro.launch.train import TrainLoop
        from repro.distributed.elastic import simulate_failure_and_recover
        cfg = ModelConfig(name="d", family="dense", n_layers=2, d_model=32,
                          n_heads=4, n_kv_heads=4, d_ff=64, vocab_size=128,
                          train_mode="lora",
                          ccm=CCMConfig(comp_len=2, max_steps=2))
        layout = M.segment_layout(2, 6, 2, 8)
        from repro.optim.adamw import AdamWConfig
        def factory(dist):
            return TrainLoop(cfg, layout, AdamWConfig(lr=1e-3,
                             total_steps=20), batch_size=8,
                             ckpt_dir={str(tmp_path)!r}, ckpt_every=4,
                             dist=None)
        mesh_a = make_debug_mesh(4, 2)   # 8 devices
        mesh_b = make_debug_mesh(2, 2)   # 'lost' half the fleet
        hist, start = simulate_failure_and_recover(
            factory, mesh_a, mesh_b, fail_after_steps=8, total_steps=12)
        assert start == 8 and len(hist) == 4
        print("OK resumed at", start)
    """)
    assert "OK" in out


def test_seq_sharded_decode():
    """SP: KV-cache sequence axis sharded over data (long-context decode)."""
    out = _run("""
        from repro.core import inference as I
        from repro.distributed import sharding as SH
        mesh = make_debug_mesh(2, 2)
        dist = make_dist(mesh)
        cfg = ModelConfig(name="d", family="dense", n_layers=2, d_model=64,
                          n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=128,
                          compute_dtype="float32",
                          ccm=CCMConfig(comp_len=2, max_steps=4))
        params = T.init_lm(jax.random.PRNGKey(0), cfg)
        state = I.init_online_state(cfg, 1, max_cache_len=64)
        state = state._replace(cache=state.cache._replace(
            length=jnp.asarray(64, jnp.int32)))
        sspec = SH.online_state_pspecs(cfg, dist, batch_sharded=False,
                                       shard_cache_seq=True)
        st_sh = SH.named(mesh, sspec)
        fn = jax.jit(lambda p, s, t: I.decode_step(p, cfg, s, t),
                     in_shardings=(None, st_sh, None))
        lg, _ = fn(params, state, jnp.ones((1, 1), jnp.int32))
        assert np.isfinite(np.asarray(lg, np.float32)).all()
        print("OK")
    """, devices=4)
    assert "OK" in out
