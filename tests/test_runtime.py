"""The launchers' process set-up: one compile-cache directory, fixed."""
import jax

from repro.launch import runtime


def test_compile_cache_uses_the_environment_when_set(monkeypatch, tmp_path):
    monkeypatch.setenv(runtime.CACHE_ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert runtime.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # nothing set


def test_compile_cache_defaults_to_the_repo(monkeypatch):
    monkeypatch.delenv(runtime.CACHE_ENV, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = runtime.enable_compile_cache()
        assert path == str(runtime.REPO_CACHE)
        assert runtime.REPO_CACHE.parent.joinpath("chip_smoke.py").is_file()
        assert jax.config.jax_compilation_cache_dir == path
        assert runtime.enable_compile_cache() == path          # stable
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_peak_bytes_unreported_on_cpu():
    assert runtime.peak_bytes_in_use() is None
