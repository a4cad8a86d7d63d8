import os
import sys

import jax
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# Tests run on the single real CPU device — the 512-device dry-run sets
# XLA_FLAGS in its own process only (see repro/launch/dryrun.py). Tests
# that need multiple devices spawn subprocesses (tests/_subproc.py).


@pytest.fixture(autouse=True, scope="module")
def _release_compiled_programs():
    """Drop JAX's compiled programs after each test file. Every CPU
    executable holds a few hundred memory maps; a worker that runs many
    files would otherwise reach the per-process map limit
    (``vm.max_map_count``) and crash inside the compiler."""
    yield
    jax.clear_caches()
