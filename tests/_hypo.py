"""One import point for ``hypothesis`` in the test modules."""
from hypothesis import given, settings
from hypothesis import strategies as st

__all__ = ["given", "settings", "st"]
