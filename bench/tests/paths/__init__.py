"""Served paths that only the tests dispatch to (``harness.PATHS``)."""
