"""Configurations of the benchmark, one module and one JSON file each."""
