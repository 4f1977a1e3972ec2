"""On-chip benchmark of the serving path: one cell (deployment x traffic
mix) per run, driven by ``BENCHMARK.json`` and the data files here."""
