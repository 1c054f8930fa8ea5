"""Fixtures of the benchmark's own tests (helpers in
``perfbench_testlib``)."""
import pytest
from perfbench_testlib import add_tiny, add_tiny_moe, copy_benchmark


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    root = copy_benchmark(str(tmp_path_factory.mktemp("bench")))
    add_tiny(root)
    return root


@pytest.fixture(scope="session")
def tiny_bench(tiny_root):
    from harness import spec
    return spec.load(tiny_root)


@pytest.fixture(scope="session")
def moe_root(tmp_path_factory):
    root = copy_benchmark(str(tmp_path_factory.mktemp("moe")))
    add_tiny(root)
    add_tiny_moe(root)
    return root
