"""Chip benchmark of the entity-resolution engine (see BENCHMARK.json)."""
