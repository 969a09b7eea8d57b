"""Chip benchmark of the served interval-filtered search (see run.py)."""
