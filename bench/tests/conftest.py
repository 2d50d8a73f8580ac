"""The benchmark's tests compile many small programs. A process of the same
test worker may have placed JAX's persistent compilation cache inside the
checkout (``launch.compile_cache``); these tests write nothing there."""
import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache


@pytest.fixture(autouse=True)
def _no_persistent_compilation_cache():
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    compilation_cache.reset_cache()
