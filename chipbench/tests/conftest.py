"""The benchmark's own tests run on the CPU, with Pallas in interpret mode:

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q

Nothing here loads an accelerator runtime when it is imported.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


import pytest  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def _compile_cache(tmp_path_factory):
    """Runs of the harness here keep their compilation cache in a temporary
    directory, not in the checkout's own."""
    from chipbench import harness

    harness.CACHE_DIR = str(tmp_path_factory.mktemp("jax_cache"))


@pytest.fixture(scope="module")
def bench():
    """BENCHMARK.json as committed; ``harness.read_cell`` finds the staged
    cells of ``chipbench/staged.json`` through it too."""
    import json

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)
