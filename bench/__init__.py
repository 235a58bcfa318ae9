"""The on-chip benchmark of this repository: see ``harness.py``."""
