"""Cell benchmark of the served packet path (see BENCHMARK.json)."""
