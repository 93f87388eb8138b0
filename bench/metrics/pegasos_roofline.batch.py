"""Share of its roofline the Pegasos stage kernel reached, %: see
``bench/readers.py``."""

from bench.readers import pegasos_roofline as read  # noqa: F401
