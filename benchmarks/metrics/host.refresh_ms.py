"""Milliseconds a proposal spent in the monitor's model refresh: span
``monitor.cluster_model`` (window aggregation, assembly, the device_put)."""
from benchlib.spans import read_spans as read  # noqa: F401
