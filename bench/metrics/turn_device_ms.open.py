"""Device time of one pool turn, ms: see ``bench/readers.py``."""

from bench.readers import turn_device_ms as read  # noqa: F401
