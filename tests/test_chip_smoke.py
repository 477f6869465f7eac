"""``chip_smoke.py`` refuses to run without a TPU, and the entry points'
compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, or else to
the fixed in-checkout directory."""

import json
import os
import pathlib
import shutil
import subprocess
import sys
import textwrap

from repro.utils import compile_cache

ROOT = pathlib.Path(__file__).resolve().parent.parent


def _run(args, cwd, **env):
    full = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    full.update(JAX_PLATFORMS="cpu", **env)
    return subprocess.run([sys.executable, *args], cwd=cwd, env=full,
                          capture_output=True, text=True, timeout=300)


def test_chip_smoke_fails_without_a_tpu():
    proc = _run(["chip_smoke.py"], ROOT)
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_chip_smoke_fails_outside_a_checkout(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    proc = _run(["chip_smoke.py"], tmp_path)
    assert proc.returncode != 0
    assert proc.stdout == ""


def test_compile_cache_defaults_to_ignored_checkout_dir():
    assert compile_cache.CACHE_DIR == ROOT / ".jax_cache"
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().splitlines()
    probe = textwrap.dedent("""
        import jax
        from repro.utils import compile_cache
        print(compile_cache.enable(), jax.config.jax_compilation_cache_dir)
    """)
    proc = _run(["-c", probe], ROOT, PYTHONPATH=str(ROOT / "src"))
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.split() == [str(compile_cache.CACHE_DIR)] * 2


def test_compile_cache_honours_env_dir(tmp_path):
    probe = textwrap.dedent("""
        import json, jax, jax.numpy as jnp
        from repro.utils import compile_cache
        where = compile_cache.enable()
        jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(8)).block_until_ready()
        print(json.dumps([where, jax.config.jax_compilation_cache_dir]))
    """)
    proc = _run(["-c", probe], ROOT, PYTHONPATH=str(ROOT / "src"),
                JAX_COMPILATION_CACHE_DIR=str(tmp_path),
                JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
                JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    assert proc.returncode == 0, proc.stderr
    assert json.loads(proc.stdout.splitlines()[-1]) == [str(tmp_path)] * 2
    assert any(tmp_path.iterdir()), "no cache entry written"
