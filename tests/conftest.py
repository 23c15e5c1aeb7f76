import os
import sys

# tests run on the single real CPU device (the dry-run sets its own flags in a
# separate process); keep compilation deterministic and quiet.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402

# the CLI entry points turn JAX's persistent compilation cache on; the tests
# that drive them must not leave compiled programs behind in the checkout
jax.config.update("jax_enable_compilation_cache", False)

from repro.launch.mesh import make_mesh  # noqa: E402

import pytest  # noqa: E402


def pytest_addoption(parser):
    parser.addoption(
        "--update-golden", action="store_true", default=False,
        help="regenerate tests/golden/*.json from the current pipeline "
             "instead of diffing against it (see tests/test_golden.py)")


@pytest.fixture(scope="session")
def mesh11():
    return make_mesh((1, 1), ("data", "model"))
