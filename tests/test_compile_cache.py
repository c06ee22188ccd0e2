"""Where the entry scripts' persistent compilation cache goes."""
import jax
from jax.experimental.compilation_cache import compilation_cache

from repro.launch import compile_cache


def test_env_dir_is_used_and_nothing_else_is_set(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    path, counter = compile_cache.enable_compile_cache()
    try:
        assert path == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
        jax.monitoring.record_event("/jax/compilation_cache/cache_misses")
        jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
        assert (counter.hits, counter.misses) == (2, 1)
    finally:
        counter.close()


def test_default_dir_is_fixed_inside_the_checkout(monkeypatch):
    """No temporary name, pid or time in the path: a later process must
    find the entries an earlier one wrote."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path, counter = compile_cache.enable_compile_cache()
        counter.close()
        again, counter = compile_cache.enable_compile_cache()
        counter.close()
        assert path == again == compile_cache.CHECKOUT_CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == path
        assert path.endswith(".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()
