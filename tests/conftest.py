"""Suite-level setup.

Puts ``src/`` on ``sys.path`` so the suite runs without PYTHONPATH=src.
"""

import gc
import pathlib
import sys

import pytest

_ROOT = pathlib.Path(__file__).resolve().parent.parent
_SRC = str(_ROOT / "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_between_modules():
    """Bound the live XLA-executable count across one long suite run.

    The suite jit-compiles several hundred distinct programs; with the
    fleet tests added, the accumulated executables can segfault the XLA
    CPU compiler late in the run (seen deterministically at
    test_simulator_dynamics inside ``backend_compile``).  Dropping the
    compiled-function caches at module boundaries keeps the process far
    from the cliff; cross-module compile reuse is minor (each module's
    shapes/configs are its own), so the runtime cost is small."""
    yield
    gc.collect()
    import jax

    jax.clear_caches()

