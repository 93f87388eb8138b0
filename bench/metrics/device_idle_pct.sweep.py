"""Device idle share of the traced window, %: see ``bench/readers.py``."""

from bench.readers import device_idle_pct as read  # noqa: F401
