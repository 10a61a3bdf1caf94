"""Placement of the persistent compile cache (runtime/compile_cache.py).

JAX is never pointed at a real cache here: ``jax.config.update`` is
replaced by a recorder, so the suite keeps the cache off.
"""
import os

import jax
import pytest

from repro.runtime import compile_cache


@pytest.mark.parametrize("env_dir", [None, "/some/dir"])
def test_cache_dir_placement(monkeypatch, env_dir):
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: updates.append((name, value)))
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)

    path = compile_cache.enable_compile_cache()

    if env_dir is None:
        # one fixed directory inside the checkout, the same every call
        assert path == compile_cache.DEFAULT_DIR
        assert path == os.path.join(compile_cache.REPO_ROOT, ".jax_cache")
        assert os.path.isfile(os.path.join(compile_cache.REPO_ROOT,
                                           "pyproject.toml"))
        assert updates == [("jax_compilation_cache_dir", path)]
    else:
        # JAX reads the variable itself; the code sets no other directory
        assert path == env_dir
        assert updates == []


def test_default_cache_dir_is_git_ignored():
    with open(os.path.join(compile_cache.REPO_ROOT, ".gitignore")) as f:
        ignored = {line.strip() for line in f}
    assert "/.jax_cache/" in ignored
