"""queue_wait_p95_s: see bench/harness/readers.py, queue_wait_p95."""
from bench.harness.readers import queue_wait_p95 as read  # noqa: F401
