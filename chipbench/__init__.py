"""On-chip benchmark of Byzantine-robust training (see run.py)."""
