"""lane_occupancy.batch: see bench/harness/readers.py, lane_occupancy."""
from bench.harness.readers import lane_occupancy as read  # noqa: F401
