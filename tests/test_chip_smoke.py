"""chip_smoke.py's refusal off the TPU, and the compile-cache policy it uses."""
import os
import subprocess
import sys

import pytest

from conftest import REPO


def _run(args, env_updates, *, drop=()):
    env = {k: v for k, v in os.environ.items() if k not in drop}
    env.update(env_updates)
    return subprocess.run(
        [sys.executable, *args], env=env, capture_output=True, text=True, timeout=300, cwd=REPO
    )


@pytest.mark.parametrize("chips", ["1", "4"])
def test_chip_smoke_exits_nonzero_off_tpu(chips):
    out = _run([os.path.join(REPO, "chip_smoke.py"), "--chips", chips], {"JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr
    assert '"ok"' not in out.stdout


@pytest.mark.parametrize("from_env", [True, False], ids=["env", "checkout"])
def test_compile_cache_dir_policy(tmp_path, from_env):
    code = (
        "import jax\n"
        "from repro.launch.compile_cache import enable_compile_cache\n"
        "print(enable_compile_cache())\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
    )
    env = {"JAX_PLATFORMS": "cpu", "PYTHONPATH": os.path.join(REPO, "src")}
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    out = _run(["-c", code], env, drop=("JAX_COMPILATION_CACHE_DIR",))
    assert out.returncode == 0, out.stderr
    want = str(tmp_path) if from_env else os.path.join(REPO, ".jax_cache")
    assert out.stdout.split() == [want, want]
