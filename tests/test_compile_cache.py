"""The entry points' persistent compile cache: the environment's directory
wins; otherwise one fixed in-checkout path, the same in every process."""
import os
import subprocess
import sys

import jax

from repro.launch import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _in_subprocess(code: str, **env_over) -> str:
    env = {k: v for k, v in os.environ.items()
           if k != compile_cache.ENV_VAR}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(REPO, "src"),
               **env_over)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    return r.stdout.strip()


def test_env_var_wins(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.compile_cache_dir() is None
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # nothing set
    # and JAX itself picks the variable up in a fresh process
    got = _in_subprocess(
        "import jax\n"
        "from repro.launch.compile_cache import enable_compile_cache\n"
        "print(enable_compile_cache(), jax.config.jax_compilation_cache_dir)",
        **{compile_cache.ENV_VAR: str(tmp_path)})
    assert got.split() == [str(tmp_path), str(tmp_path)]


def test_fallback_is_one_fixed_checkout_path(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    first = compile_cache.compile_cache_dir()
    assert first == compile_cache.compile_cache_dir()
    assert first == os.path.join(REPO, ".jax_cache")
    code = ("import jax\n"
            "from repro.launch.compile_cache import enable_compile_cache\n"
            "print(enable_compile_cache(), "
            "jax.config.jax_compilation_cache_dir)")
    runs = [_in_subprocess(code).split() for _ in range(2)]
    assert runs[0] == runs[1] == [first, first]
