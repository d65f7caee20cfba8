"""On-chip benchmark of the iterative-GP fit path (see bench/run.py)."""
