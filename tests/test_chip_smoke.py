"""chip_smoke.py rehearsed on the CPU: every phase runs at a tiny
size and the replies match the model, yet the run can NOT pass — the
smoke's "ok" is reserved for a TPU.  Plus the compile-cache helper
every entry point shares."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cpu_env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(extra)
    return env


def test_chip_smoke_runs_every_phase_on_cpu_and_cannot_pass(tmp_path):
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"),
         "--n-ens", "64", "--n-slots", "16", "--keys", "500",
         "--out", str(tmp_path)],
        capture_output=True, text=True, timeout=600, env=_cpu_env())
    lines = proc.stdout.strip().splitlines()
    assert proc.returncode != 0, proc.stdout
    assert "NOT A TPU" in lines[0] and "NOT A TPU" in lines[-1], lines
    assert '"ok": true' not in proc.stdout
    out = proc.stdout
    # every phase up to the Mosaic kernel passed against the model ...
    for phase in ("native", "step", "serve", "restore"):
        assert f"phase {phase}: ok" in out, out + proc.stderr
    assert "'wire': True" in out and "'enqueue': True" in out
    assert "keys read back after restore(), all equal" in out
    assert "replies checked against the dict model" in out
    # ... and the kernel phase ran, and failed: no interpreter stands
    # in for Mosaic unless a test asks for it
    assert "phase pallas: FAILED" in out
    assert "Only interpret mode is supported on CPU" in proc.stderr
    for fact in ("compile cache:", "compiles before_first_reply:",
                 "compiles during_serving:", "peak device bytes:",
                 '"donate": false', "full_step_donate step_ms="):
        assert fact in out, (fact, out)


def test_chip_smoke_refuses_the_full_shape_without_a_tpu(tmp_path):
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"),
         "--out", str(tmp_path)],
        capture_output=True, text=True, timeout=120, env=_cpu_env())
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU, nothing run" in proc.stdout


@pytest.mark.parametrize("placed", [True, False])
def test_compile_cache_is_placed_from_outside(tmp_path, placed):
    """JAX_COMPILATION_CACHE_DIR wins and nothing is set in code;
    without it the cache sits at the fixed <checkout>/.jax_cache.  (A
    child process: the variable binds when jax is imported.)"""
    env = _cpu_env(**({"JAX_COMPILATION_CACHE_DIR": str(tmp_path)}
                      if placed else {}))
    code = ("import jax\n"
            "from riak_ensemble_tpu.utils.jaxcache import "
            "setup_compile_cache\n"
            "print(setup_compile_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          capture_output=True, text=True, timeout=120,
                          env=env)
    assert proc.returncode == 0, proc.stderr
    want = str(tmp_path) if placed else os.path.join(REPO, ".jax_cache")
    assert proc.stdout.split() == [want, want]
