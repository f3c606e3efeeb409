"""Metric readers, one module per metric, loaded by name (``harness``)."""
