"""ttft_p95_s: see bench/harness/readers.py, ttft_p95."""
from bench.harness.readers import ttft_p95 as read  # noqa: F401
