"""The chip benchmark of the LeaFi serving path (see BENCHMARK.json)."""
