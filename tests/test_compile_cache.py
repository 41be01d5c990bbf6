"""The persistent compilation cache's directory (``repro.launch.cache``):
``$JAX_COMPILATION_CACHE_DIR`` when set, else one fixed, git-ignored path
in the checkout. Only the path is resolved here; tests never turn the cache
on."""
import os
import subprocess

from repro.launch import cache


def test_honours_env_var(monkeypatch, tmp_path):
    monkeypatch.setenv(cache.ENV_VAR, str(tmp_path))
    assert cache.compile_cache_dir() == str(tmp_path)


def test_default_is_fixed_and_ignored(monkeypatch):
    monkeypatch.delenv(cache.ENV_VAR, raising=False)
    first = cache.compile_cache_dir()
    assert first == cache.compile_cache_dir()
    root = os.path.dirname(first)
    assert os.path.isfile(os.path.join(root, "src", "repro", "launch",
                                       "cache.py"))
    out = subprocess.run(["git", "check-ignore", "-q",
                          os.path.join(first, "entry")], cwd=root)
    assert out.returncode in (0, 128)   # 128: not a git checkout
