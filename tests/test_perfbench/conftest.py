import pytest


@pytest.fixture(scope="module")
def out_dir(tmp_path_factory):
    """The harness writes under a temporary directory, and JAX's cache settings, which
    the program's entry changes for the whole process, are put back afterwards."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from perfbench import harness

    names = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs", "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    old, harness.OUT = harness.OUT, tmp_path_factory.mktemp("perfbench_out")
    yield harness.OUT
    harness.OUT = old
    for n, v in saved.items():
        jax.config.update(n, v)
    compilation_cache.reset_cache()
