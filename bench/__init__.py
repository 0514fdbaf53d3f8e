"""Chip benchmark of the hosting engine (see ``BENCHMARK.json``)."""
