"""Shared test plumbing: skip the `interpret` kernel lane cleanly when
Pallas (or its TPU interpret mode) is not importable in this environment."""
import pytest


def _interpret_supported() -> bool:
    try:
        from jax.experimental import pallas  # noqa: F401
        from jax.experimental.pallas import tpu  # noqa: F401
        return True
    except ImportError:
        # ONLY a missing Pallas skips the lane; any other failure must
        # surface as loud test errors, not an all-green all-skipped kernel
        # lane.
        return False


def pytest_collection_modifyitems(config, items):
    if _interpret_supported():
        return
    skip = pytest.mark.skip(reason="Pallas interpret mode unavailable")
    for item in items:
        if "interpret" in item.keywords:
            item.add_marker(skip)
