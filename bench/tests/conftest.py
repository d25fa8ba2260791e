"""CPU tests of the benchmark: no chip, no TPU library, nothing written
into the compile cache."""

import os
import shutil
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402


def make_tree(dst):
    """A checkout-shaped copy of the benchmark under ``dst`` whose
    BENCHMARK.json names the tiny CPU cells of ``tests/data``."""
    shutil.copytree(BENCH, os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    data = os.path.join(HERE, "data")
    shutil.copy(os.path.join(data, "BENCHMARK.json"), dst)
    for sub in ("configs", "traffic"):
        for f in os.listdir(os.path.join(data, sub)):
            shutil.copy(os.path.join(data, sub, f),
                        os.path.join(dst, "bench", sub, f))
    return str(dst)


@pytest.fixture
def tree(tmp_path):
    return make_tree(tmp_path)


@pytest.fixture
def no_compile_cache(monkeypatch):
    """The harness turns the persistent compile cache on; tests keep it
    off so nothing lands in the checkout's cache directory."""
    import repro.session
    monkeypatch.setattr(repro.session, "enable_compilation_cache",
                        lambda: None)
