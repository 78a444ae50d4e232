"""Fixtures of the benchmark's own tests."""

import pytest


@pytest.fixture(scope="module")
def tiny_compile_cache(tmp_path_factory):
    import bench_tiny
    with bench_tiny.compile_cache(tmp_path_factory.mktemp("jax_cache")):
        yield
