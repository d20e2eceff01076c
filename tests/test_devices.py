"""repro.devices: the one persistent compile cache and platform queries
that never read a failure as "not a TPU"."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro import devices

ROOT = Path(__file__).resolve().parents[1]

_PROBE = """
import json, jax, jax.numpy as jnp
from repro.devices import ensure_compile_cache
ensure_compile_cache()
jax.jit(lambda x: x * 3 + 1)(jnp.arange(5.0)).block_until_ready()
print(json.dumps(jax.config.jax_compilation_cache_dir))
"""


@pytest.mark.parametrize("env_dir", [True, False], ids=["env", "checkout"])
def test_compile_cache_dir_is_env_else_fixed_checkout_path(tmp_path, env_dir):
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    out = subprocess.run(
        [sys.executable, "-c", _PROBE], env=env, cwd=tmp_path,
        capture_output=True, text=True, timeout=120, check=True,
    )
    used = json.loads(out.stdout.strip().splitlines()[-1])
    if env_dir:
        assert used == str(tmp_path / "cache")
        assert any((tmp_path / "cache").iterdir()), "no cache entry written"
    else:
        assert used == devices.DEFAULT_CACHE_DIR == str(ROOT / ".jax_cache")


def test_platform_query_raises_instead_of_reading_not_tpu(monkeypatch):
    import jax

    def broken():
        raise RuntimeError("backend failed to initialize")

    monkeypatch.setattr(jax, "default_backend", broken)
    with pytest.raises(RuntimeError, match="failed to initialize"):
        devices.on_tpu()
