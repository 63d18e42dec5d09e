"""chip_smoke.py rehearsed on the CPU: its work functions at a tiny size,
its refusal to run without a TPU, and the compile-cache placement its
entry point makes."""

import os
import pathlib
import subprocess
import sys

import jax

import chip_smoke
from fluidframework_tpu.utils import compile_cache

ROOT = pathlib.Path(__file__).resolve().parents[1]


def test_smoke_work_function_on_cpu():
    """The served catch-up (TCP RPC, cold batches + a warm repeat) and the
    map/matrix/tree phase: byte-identical to the oracles, nothing on the
    CPU container path, every answer naming the platform it folded on."""
    out = chip_smoke.run_smoke(n_docs=40, ops_per_doc=24, batch_docs=16,
                               kernel_docs=6, sample=8, platform="cpu")
    served = out["catchup"]
    assert served["deviceDocs"] == 40 and served["cpuDocs"] == 0
    assert served["platforms"] == ["cpu"]
    assert served["oracle_sample"] >= 8
    assert set(out["kernels"]) == {"map", "matrix", "tree"}
    assert all(k["device_docs"] == 6 for k in out["kernels"].values())
    assert "catchup-warm-0" in out["phases"]


def test_mesh_work_function_on_virtual_devices():
    """``--chips`` path: the auto doc mesh over every visible device
    equals one device and the oracle, and no device is left idle."""
    n = len(jax.devices())
    out = chip_smoke.run_mesh(n_docs=8 * n + 3, ops_per_doc=16, sample=6,
                              n_devices=n, platform="cpu")
    assert len(out["docs_per_device"]) == n
    assert sum(out["docs_per_device"].values()) == 8 * n + 3


def test_smoke_refuses_to_run_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=ROOT, capture_output=True,
        text=True, timeout=120, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert "platform 'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_compile_cache_defaults_to_the_repo(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        placed = compile_cache.setup_compile_cache()
        assert placed == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == placed
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    ignored = (ROOT / ".gitignore").read_text(encoding="utf-8").split()
    assert ".jax_cache/" in ignored


def test_compile_cache_placed_from_outside(tmp_path):
    """With ``JAX_COMPILATION_CACHE_DIR`` set, nothing is set in code and
    the compiled program lands in that directory."""
    code = ("import jax, jax.numpy as jnp\n"
            "from fluidframework_tpu.utils.compile_cache import "
            "setup_compile_cache\n"
            "print(setup_compile_cache())\n"
            "jax.jit(lambda x: x * 3)(jnp.arange(8)).block_until_ready()\n")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path),
           "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"}
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.split() == [str(tmp_path)]
    assert any(tmp_path.iterdir()), "nothing written to the cache dir"
