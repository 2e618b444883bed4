"""The benchmark: ``python3 benchmarks/run.py`` (see README.md beside this file)."""
