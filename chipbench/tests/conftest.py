"""The benchmark's CPU tests import ``chipbench`` from the checkout's root
and the program from ``src``."""
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


import pytest  # noqa: E402

from chipbench_tiny import make_tiny  # noqa: E402


@pytest.fixture(scope="module")
def tiny_bench(tmp_path_factory):
    """BENCHMARK.json of a CPU-sized copy of the benchmark."""
    return make_tiny(tmp_path_factory.mktemp("checkout"))
