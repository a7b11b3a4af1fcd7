"""Chip benchmark of the graph generators: see ``bench/run.py``."""
