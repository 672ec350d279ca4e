"""Where the entry points put JAX's persistent compilation cache."""
import os
import subprocess
import sys

import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


@pytest.mark.parametrize("env", [None, "/elsewhere/jax-cache"])
def test_cache_dir_from_env_or_fixed_checkout_path(monkeypatch,
                                                   restore_cache_dir, env):
    if env is None:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    else:
        monkeypatch.setenv(compile_cache.ENV_VAR, env)
    before = jax.config.jax_compilation_cache_dir
    got = compile_cache.enable_compile_cache()
    if env is None:
        # the same in-checkout path on every call, git-ignored
        assert got == str(compile_cache.DEFAULT_DIR)
        assert got == compile_cache.enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == got
        assert compile_cache.DEFAULT_DIR.parent.joinpath("src").is_dir()
    else:
        # JAX reads the variable itself; nothing else is set
        assert got == env
        assert jax.config.jax_compilation_cache_dir == before


def test_importing_repro_leaves_the_cache_off():
    env = {k: v for k, v in os.environ.items()
           if k != compile_cache.ENV_VAR}
    code = ("import jax, repro.api, repro.launch.compile_cache\n"
            "print(jax.config.jax_compilation_cache_dir)")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300,
                         env={**env, "JAX_PLATFORMS": "cpu"})
    assert out.stdout.strip().splitlines()[-1] == "None", out.stderr
