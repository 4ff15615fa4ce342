"""Chip benchmark of the subsampling service (see PERF.md)."""
