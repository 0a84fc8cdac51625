"""Metric readers, one module per metric name: ``read(run)`` returns the
number, or None where the run holds nothing to read."""
