"""tpot_p95_s: see bench/harness/readers.py, tpot_p95."""
from bench.harness.readers import tpot_p95 as read  # noqa: F401
