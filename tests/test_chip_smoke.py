"""chip_smoke.py on the CPU: its phases at the smoke config, and its
refusal to run without a TPU."""
import importlib.util
import os
import subprocess
import sys

import jax
import numpy as np

from repro.configs.registry import get_config
from repro.models import transformer as T

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_serve_phase_at_smoke_config():
    """Every request kind the chip run sends is served, and each query
    matches the direct ingest_context + prefill path."""
    smoke = _load_smoke()
    cfg = get_config("qwen2-0.5b", smoke=True)
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    report = smoke.serve_phase(cfg, params, seed=0)
    stats = report["stats"]
    assert stats["ingest"]["requests"] == 8      # 4 sessions x 2 chunks
    assert stats["query"]["requests"] == 6       # 4 + prefix hit + fork
    assert stats["stream"]["requests"] == 6
    assert report["compiled_programs"] >= 3
    assert np.isfinite(report["max_abs_err"])
    assert {"ingest_1", "fork_query", "stream", "direct_check"} \
        <= set(report["timings"])


def test_chip_smoke_kernel_phase_in_interpret_mode(monkeypatch):
    """The arena-kernel check at the smoke config, with the kernels in
    the Pallas interpreter (the script itself compiles them)."""
    from repro.kernels import ops
    smoke = _load_smoke()
    gather, scatter = ops.session_gather, ops.session_scatter
    monkeypatch.setattr(ops, "session_gather", lambda s, i, interpret:
                        gather(s, i, interpret=True))
    monkeypatch.setattr(ops, "session_scatter", lambda s, i, r, interpret:
                        scatter(s, i, r, interpret=True))
    cfg = get_config("qwen2-0.5b", smoke=True)
    assert smoke.kernel_phase(cfg) > 0


def test_chip_smoke_refuses_cpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    r = subprocess.run([sys.executable, SCRIPT], env=env, cwd=tmp_path,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "no TPU found" in r.stderr
    assert r.stdout.strip() == ""


def test_chip_smoke_sharded_phase_on_four_host_devices():
    """The --chips 4 phase at the smoke config on 4 forced CPU devices
    (a subprocess: the device count is fixed when JAX starts)."""
    body = f"""
import importlib.util, jax
from repro.configs.registry import get_config
from repro.models import transformer as T
spec = importlib.util.spec_from_file_location("chip_smoke", {SCRIPT!r})
smoke = importlib.util.module_from_spec(spec)
spec.loader.exec_module(smoke)
assert jax.device_count() == 4
cfg = get_config("qwen2-0.5b", smoke=True)
report = smoke.sharded_phase(cfg, T.init_lm(jax.random.PRNGKey(0), cfg))
print("SHARDED", report["max_abs_err"])
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run([sys.executable, "-c", body], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    assert "SHARDED 0.0" in r.stdout        # bit-exact on the CPU


def test_chip_smoke_compile_cache_placement(tmp_path):
    """Unset JAX_COMPILATION_CACHE_DIR: the fixed in-checkout path; set:
    JAX's own reading of the variable, untouched by the script."""
    body = f"""
import importlib.util, jax
spec = importlib.util.spec_from_file_location("chip_smoke", {SCRIPT!r})
smoke = importlib.util.module_from_spec(spec)
spec.loader.exec_module(smoke)
smoke._configure_compile_cache()
print("CACHE", jax.config.jax_compilation_cache_dir)
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = {}
    for name, extra in (("unset", {}),
                        ("set", {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})):
        r = subprocess.run([sys.executable, "-c", body], env={**env, **extra},
                           cwd=tmp_path, capture_output=True, text=True,
                           timeout=300)
        assert r.returncode == 0, r.stderr
        out[name] = r.stdout.split("CACHE ", 1)[1].strip()
    assert out["unset"] == os.path.join(REPO, ".jax_cache")
    assert out["set"] == str(tmp_path)
