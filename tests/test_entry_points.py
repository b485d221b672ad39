"""Entry-point plumbing: the persistent compile cache location, and
``chip_smoke.py`` refusing to run without a TPU."""

import os
import subprocess
import sys

from conftest import REPO_ROOT, subprocess_env

_CACHE_DIR_SCRIPT = (
    "import jax\n"
    "from repro.launch.compile_cache import enable_compile_cache\n"
    "got = enable_compile_cache()\n"
    "print(got)\n"
    "print(jax.config.jax_compilation_cache_dir)\n"
)


def _cache_dirs(env):
    proc = subprocess.run([sys.executable, "-c", _CACHE_DIR_SCRIPT], capture_output=True,
                          text=True, timeout=120, env=env, cwd=REPO_ROOT)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stdout.split()[-2:]


def test_compile_cache_defaults_to_fixed_dir_in_checkout():
    env = subprocess_env()
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    returned, configured = _cache_dirs(env)
    assert returned == configured == os.path.join(REPO_ROOT, ".jax_cache")
    assert _cache_dirs(env) == [returned, configured]   # the same path every run


def test_compile_cache_env_var_wins(tmp_path):
    env = dict(subprocess_env(), JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert _cache_dirs(env) == [str(tmp_path), str(tmp_path)]


def test_chip_smoke_fails_without_tpu():
    env = dict(subprocess_env(), JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], capture_output=True, text=True,
                          timeout=120, env=env, cwd=REPO_ROOT)
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    assert '"ok"' not in proc.stdout
