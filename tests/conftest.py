import os
import sys

import pytest

# src layout without installation
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# Tests run on the real (single) CPU device unless a lane forces more
# host devices through XLA_FLAGS.
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def pytest_collection_modifyitems(config, items):
    """Keep the property-fuzz lane out of tier-1: `fuzz`-marked tests only
    run when explicitly selected (pytest -m fuzz), so the exact ROADMAP
    tier-1 command stays fast and dependency-light.  The marker is named
    `fuzz`, NOT `hypothesis`, because the hypothesis pytest plugin
    auto-applies a `hypothesis` marker to every @given test — reusing that
    name would silently deselect the pre-existing property tests from
    tier-1 wherever hypothesis is installed."""
    markexpr = config.getoption("-m", default="") or ""
    if "fuzz" in markexpr:
        return
    skip = pytest.mark.skip(reason="property-fuzz lane: run `pytest -m fuzz`")
    for item in items:
        if "fuzz" in item.keywords:
            item.add_marker(skip)
