"""On-chip benchmark of the vecsim deployment path (see ``BENCHMARK.json``)."""
