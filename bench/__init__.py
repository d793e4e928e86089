"""On-chip benchmark of the IPComp codec (see ``BENCHMARK.json``)."""
