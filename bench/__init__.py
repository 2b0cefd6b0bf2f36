"""Benchmark of the served constrained-kNN path (see ``run.py``)."""
