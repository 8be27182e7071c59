"""The chip benchmark: one harness, cells found by name (see harness.py)."""
