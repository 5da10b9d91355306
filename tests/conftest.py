import numpy as np
import pytest

# NOTE: no XLA_FLAGS here — smoke tests and benches must see ONE device.
# Multi-device tests spawn subprocesses that set the flag themselves.

# The container image has no `hypothesis`; install the seeded-sampling
# fallback so the property-test files collect and run (see
# _hypothesis_fallback.py). A real install always wins.
try:
    import hypothesis  # noqa: F401
except ModuleNotFoundError:
    import _hypothesis_fallback

    _hypothesis_fallback._install()


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running test")


@pytest.fixture
def rng():
    return np.random.default_rng(12345)


@pytest.fixture
def as_form(tmp_path):
    """``as_form(index, form)``: a ``StringIndex`` as ``"built"`` (returned
    unchanged) or ``"loaded"``: saved and read back with ``StringIndex.load``,
    the form the on-chip benchmark serves (a loaded index's device arrays
    come from the snapshot, and it has no host builder until a merge)."""
    from repro.index import StringIndex

    def convert(index, form):
        if form == "built":
            return index
        assert form == "loaded", form
        path = str(tmp_path / f"as-form-{id(index)}.npz")
        index.save(path)
        return StringIndex.load(path, index.config)

    return convert
