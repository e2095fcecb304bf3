"""A benchmark of DSE queries on the chip (see BENCHMARK.json and run.py)."""
