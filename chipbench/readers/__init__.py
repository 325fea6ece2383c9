"""One reader per way of taking a per-layer metric from a run's result."""
