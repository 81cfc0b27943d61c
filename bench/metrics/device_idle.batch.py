"""device_idle.batch: see bench/harness/readers.py, device_idle."""
from bench.harness.readers import device_idle as read  # noqa: F401
