"""On-chip benchmark of PMV: one cell per run (see run.py)."""
