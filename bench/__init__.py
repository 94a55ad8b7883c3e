"""The chip benchmark: see bench/cell.py and PERF.md."""
