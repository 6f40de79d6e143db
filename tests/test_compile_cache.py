"""The entry points' persistent compilation cache location
(``repro.launch.compile_cache``): ``JAX_COMPILATION_CACHE_DIR`` when set,
otherwise one fixed directory inside the checkout."""
import pytest

jax = pytest.importorskip("jax")

from repro.launch import compile_cache  # noqa: E402


@pytest.fixture
def cache_config():
    """Restore JAX's cache directory setting after the test."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


@pytest.mark.parametrize("env", [None, "/some/cache/dir"])
def test_cache_dir(monkeypatch, cache_config, env):
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
    before = jax.config.jax_compilation_cache_dir
    got = compile_cache.setup_compile_cache()
    if env is None:
        want = str(compile_cache.DEFAULT_CACHE_DIR)
        assert got == want == jax.config.jax_compilation_cache_dir
        assert compile_cache.DEFAULT_CACHE_DIR.parent.joinpath(
            "chip_smoke.py").is_file()         # the checkout's root
    else:
        # JAX's own reading of the variable stands; nothing is overridden
        assert got == env
        assert jax.config.jax_compilation_cache_dir == before
