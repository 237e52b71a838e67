"""Where the entry points keep JAX's persistent compilation cache."""

import os

from spark_rapids_jni_tpu import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_cache_dir_follows_the_environment_variable(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.cache_dir() == str(tmp_path)
    # enable() sets nothing when the variable is set: JAX reads it itself
    import jax

    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_dir_defaults_to_the_ignored_repo_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.cache_dir()
    assert path == os.path.join(REPO, ".jax_cache")
    assert compile_cache.cache_dir({}) == path  # fixed: no pid, time or tmp
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache*/" in f.read().split()
