"""tokens_per_s: see bench/harness/readers.py, tokens_per_s."""
from bench.harness.readers import tokens_per_s as read  # noqa: F401
