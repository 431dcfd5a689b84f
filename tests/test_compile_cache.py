"""The persistent compilation cache can be placed from outside
(spark_rapids_tpu/runtime/compile_cache.py): the environment decides, and
otherwise the path is fixed — no fingerprint, pid, time or temp name in it."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_PROBE = ("from spark_rapids_tpu.runtime import compile_cache; import jax; "
          "print(compile_cache.enable()); "
          "print(jax.config.jax_compilation_cache_dir)")


def _enable_in_child(env_dir, cwd):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c", _PROBE], env=env, cwd=cwd,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    returned, configured = out.stdout.split()[-2:]
    return returned, configured


def test_environment_places_the_cache(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: the helper leaves JAX's setting at
    that value and sets no other directory in code."""
    want = str(tmp_path / "outside")
    assert _enable_in_child(want, str(tmp_path)) == (want, want)


def test_default_is_a_fixed_path_in_the_checkout(tmp_path):
    """Unset: <checkout>/.jax_cache, the same from two processes started in
    different directories."""
    want = os.path.join(REPO, ".jax_cache")
    first = _enable_in_child(None, str(tmp_path))
    second = _enable_in_child(None, REPO)
    assert first == second == (want, want)


def test_failures_raise(monkeypatch):
    """No `except: pass` around the set-up: a broken config update surfaces."""
    import jax
    from spark_rapids_tpu.runtime import compile_cache

    def boom(*a, **k):
        raise RuntimeError("config refused")
    monkeypatch.setattr(jax.config, "update", boom)
    with pytest.raises(RuntimeError, match="config refused"):
        compile_cache.enable()
