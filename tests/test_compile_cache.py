"""Where entry points put JAX's persistent compilation cache."""
from repro.launch import compile_cache


def _recorded_updates(monkeypatch):
    calls = []
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    return calls


def test_environment_directory_wins_and_nothing_is_set(monkeypatch,
                                                       tmp_path):
    calls = _recorded_updates(monkeypatch)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert calls == []


def test_default_is_the_fixed_repo_directory(monkeypatch):
    calls = _recorded_updates(monkeypatch)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = str(compile_cache.REPO_CACHE_DIR)
    assert compile_cache.enable_compile_cache() == want
    assert compile_cache.enable_compile_cache() == want
    assert calls == [("jax_compilation_cache_dir", want)] * 2
    assert want.endswith(".jax_cache")
    assert (compile_cache.REPO_CACHE_DIR.parent / "chip_smoke.py").exists()
