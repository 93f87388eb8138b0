"""Host time of one pool step, ms: see ``bench/readers.py``."""

from bench.readers import pool_step_ms as read  # noqa: F401
