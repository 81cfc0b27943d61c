"""mfu.batch: see bench/harness/readers.py, mfu."""
from bench.harness.readers import mfu as read  # noqa: F401
