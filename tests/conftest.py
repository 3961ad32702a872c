"""Shared test-session plumbing.

Clearing JAX's compilation caches whenever a test process crosses a
test-package boundary keeps its live-executable count bounded to one
package's worth, without changing any test. It was added after an earlier
JAX's CPU backend segfaulted inside ``backend_compile`` once ~270 jitted
executables had accumulated in a single-process tier-1 run (every
package-level subset passed alone, so it was a compiler-state cliff, not
a test bug or OOM). It stays under jax 0.9: no single-process run of the
whole suite without it has been made to show the cliff is gone, and it
also caps each xdist worker's memory, which matters on a shared host.
"""
import jax
import pytest

_last_pkg = [None]


def _package(item) -> str:
    parts = str(item.fspath).split("/")
    if "tests" in parts:
        i = parts.index("tests")
        if i + 2 < len(parts):
            return parts[i + 1]
    return str(item.fspath)


@pytest.fixture(autouse=True)
def _clear_jax_caches_between_packages(request):
    pkg = _package(request.node)
    if _last_pkg[0] is not None and pkg != _last_pkg[0]:
        jax.clear_caches()
    _last_pkg[0] = pkg
    yield


@pytest.fixture
def dispatch_counters():
    """Fresh view over the obs/ dispatch-counter registry (the trace-time
    flash/paged/vq/matmul impl counters). Counters are zeroed before the
    test — so assertions are absolute counts, not before/after deltas —
    and zeroed again afterwards so no test inherits another's tallies.
    Yields ``snapshot_dispatch_counters`` (a deep-copying callable:
    ``counts()["vq"]["pallas"]``)."""
    from repro.obs import reset_dispatch_counters, snapshot_dispatch_counters

    reset_dispatch_counters()
    yield snapshot_dispatch_counters
    reset_dispatch_counters()
