"""tick_ms.serve: see bench/harness/readers.py, tick_ms."""
from bench.harness.readers import tick_ms as read  # noqa: F401
