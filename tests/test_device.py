"""The platform decisions in ``repro.device``."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.device import interpret_kernels

REPO = Path(__file__).resolve().parent.parent


def test_kernels_interpreted_only_off_tpu():
    assert interpret_kernels() == (jax.default_backend() != "tpu")
    assert interpret_kernels(False) is False
    assert interpret_kernels(True) is True


@pytest.mark.parametrize("from_env", [False, True])
def test_compile_cache_dir(tmp_path, from_env):
    """The cache goes where JAX_COMPILATION_CACHE_DIR says, else to a fixed
    directory in the checkout. Run in a child: the test process itself
    never turns the cache on."""
    env = {**os.environ, "PYTHONPATH": str(REPO / "src"),
           "JAX_PLATFORMS": "cpu"}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = REPO / ".jax_cache"
    if from_env:
        want = tmp_path / "cache"
        env["JAX_COMPILATION_CACHE_DIR"] = str(want)
    code = ("import jax; from repro.device import enable_compile_cache; "
            "print(enable_compile_cache()); "
            "print(jax.config.jax_compilation_cache_dir)")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120,
                         check=True).stdout.split()
    assert out == [str(want), str(want)]
