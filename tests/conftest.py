import contextlib
import os

import pytest

# Tests run on the default single CPU device (the dry-run sets its own
# device count in its own process). Keep hypothesis deterministic.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

try:
    from hypothesis import settings
except ModuleNotFoundError:
    # hypothesis is an optional `test` extra (pip install -e .[test]);
    # property tests skip via the tests/_hyp.py shim when it is missing.
    settings = None

if settings is not None:
    settings.register_profile("ci", max_examples=25, deadline=None,
                              derandomize=True)
    settings.load_profile("ci")


@pytest.fixture
def dense_mix(monkeypatch):
    """A context manager under which a new `DDASimulator` takes the dense
    P @ z matmul whatever its graph: the reference the tests hold the
    sparse gather to. The simulator picks its mix from the graph; only a
    test overrides that."""
    from repro.core.dda import DDASimulator

    @contextlib.contextmanager
    def forced():
        with monkeypatch.context() as m:
            m.setattr(DDASimulator, "_resolve_mix_mode",
                      lambda self: "dense")
            yield

    return forced
