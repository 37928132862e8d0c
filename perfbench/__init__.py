"""Chip benchmark of the private ADMM protocol (see perfbench/run.py)."""
