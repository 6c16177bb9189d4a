"""End-to-end launcher tests (subprocess, smoke configs)."""
import os
import subprocess
import sys


def _run(args, timeout=900, **env):
    # the launchers turn on the persistent compile cache; tests leave it off
    env = {**os.environ, "PYTHONPATH": "src",
           "JAX_ENABLE_COMPILATION_CACHE": "false", **env}
    out = subprocess.run([sys.executable] + args, capture_output=True,
                         text=True, timeout=timeout, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_train_launcher_runs_and_resumes(tmp_path):
    ckpt = str(tmp_path / "ckpt")
    out = _run(["-m", "repro.launch.train", "--arch", "qwen2-0.5b", "--smoke",
                "--steps", "8", "--batch", "2", "--seq", "32",
                "--save-every", "4", "--log-every", "4",
                "--ckpt-dir", ckpt])
    assert "step     8" in out and "done" in out
    # resume: starts from step 8, ends immediately
    out2 = _run(["-m", "repro.launch.train", "--arch", "qwen2-0.5b", "--smoke",
                 "--steps", "8", "--batch", "2", "--seq", "32",
                 "--save-every", "4", "--ckpt-dir", ckpt])
    assert "start_step=8" in out2


def test_serve_launcher(tmp_path):
    out = _run(["-m", "repro.launch.serve", "--arch", "qwen2-0.5b", "--smoke",
                "--requests", "4", "--max-new", "3"])
    assert "device=" in out and "rps=" in out and "p99=" in out


def test_dryrun_single_cell(tmp_path):
    out_json = str(tmp_path / "dry.json")
    out = _run(["-m", "repro.launch.dryrun", "--arch", "qwen2-0.5b",
                "--shape", "decode_32k", "--mesh", "pod1",
                "--out", out_json], timeout=1200)
    assert "1 ok" in out
    import json
    with open(out_json) as f:
        rec = json.load(f)["qwen2-0.5b|decode_32k|pod1"]
    assert rec["status"] == "ok"
    assert rec["n_chips"] == 256
    assert rec["roofline"]["dominant"] in ("compute", "memory", "collective")
    assert os.listdir(tmp_path / "hlo")       # HLO kept beside --out


_CACHE_PROBE = """
import os, jax, jax.numpy as jnp
from repro.launch.compile_cache import CHECKOUT, enable_compile_cache
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
print(enable_compile_cache())
if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()
print(CHECKOUT)
"""


def test_compile_cache_in_the_directory_the_environment_names(tmp_path):
    cache = tmp_path / "cache"
    out = _run(["-c", _CACHE_PROBE], JAX_ENABLE_COMPILATION_CACHE="true",
               JAX_COMPILATION_CACHE_DIR=str(cache)).split()
    assert out[0] == str(cache)
    assert os.listdir(cache)                  # the compile was written there


def test_compile_cache_defaults_to_the_checkout(tmp_path):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    out = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env={
        **env, "PYTHONPATH": "src", "JAX_ENABLE_COMPILATION_CACHE": "false"},
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-3000:]
    cache_dir, checkout = out.stdout.split()
    assert cache_dir == os.path.join(checkout, ".jax_cache")
    assert os.path.exists(os.path.join(checkout, "chip_smoke.py"))
