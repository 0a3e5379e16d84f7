"""The on-chip benchmark of the graph system: see ``bench/run.py``."""
