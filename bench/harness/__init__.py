"""What every cell shares: loading by name, traffic, the drive, the trace
reduction, the reference check and the metric arithmetic."""
