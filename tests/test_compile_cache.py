"""The persistent compile cache has one location, settable from outside:
``JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache`` from
any working directory.  Each case runs in a fresh interpreter, as JAX reads
the variable when it is imported."""

import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = (
    "import jax\n"
    "from repro.kernels.autotune import enable_compilation_cache\n"
    "d = enable_compilation_cache()\n"
    "print(d)\n"
    "print(jax.config.jax_compilation_cache_dir)\n"
)


@pytest.mark.parametrize("from_env", [True, False])
def test_compilation_cache_dir(tmp_path, from_env):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    want = os.path.join(ROOT, ".jax_cache")
    if from_env:
        want = str(tmp_path / "from_env")
        env["JAX_COMPILATION_CACHE_DIR"] = want
    cwd = tmp_path / "elsewhere"
    cwd.mkdir()
    cp = subprocess.run([sys.executable, "-c", _PROBE], cwd=cwd, env=env,
                        capture_output=True, text=True, timeout=120)
    assert cp.returncode == 0, cp.stderr
    returned, configured = cp.stdout.split()
    assert returned == configured == want
    assert not (cwd / ".jax_cache").exists()
