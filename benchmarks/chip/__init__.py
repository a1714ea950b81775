"""On-chip benchmark of the serving and training paths (see BENCHMARK.json)."""
