"""The chip benchmark: harness, configurations, mixes and metric readers."""
