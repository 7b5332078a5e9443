"""On-chip benchmark of the sparse serving path (see PERF.md)."""
