"""attn_share.batch: see bench/harness/readers.py, attn_share."""
from bench.harness.readers import attn_share as read  # noqa: F401
