"""On-chip benchmark harness."""
