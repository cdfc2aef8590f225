"""The persistent compilation cache goes where enable_compile_cache says.

Each case runs in a fresh interpreter: JAX sets its cache up once per
process, at the first compile.
"""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def test_default_dir_is_inside_the_checkout():
    from repro.launch import compile_cache

    assert (os.path.normpath(compile_cache.REPO_CACHE_DIR)
            == os.path.join(ROOT, ".jax_cache"))


@pytest.mark.parametrize("env_set", [True, False])
def test_cache_is_written_to_one_dir(tmp_path, env_set):
    env_dir, repo_dir = tmp_path / "env", tmp_path / "repo"
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.join(ROOT, "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_set:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    code = textwrap.dedent(f"""
        import jax, jax.numpy as jnp
        from repro.launch import compile_cache
        compile_cache.REPO_CACHE_DIR = {str(repo_dir)!r}
        print(compile_cache.enable_compile_cache())
        jax.jit(lambda x: x * 2 + 1)(jnp.arange(8.0)).block_until_ready()
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    want, other = (env_dir, repo_dir) if env_set else (repo_dir, env_dir)
    assert out.stdout.strip() == str(want)
    assert want.is_dir() and any(want.iterdir())
    assert not other.exists()
