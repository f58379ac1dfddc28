"""On-chip benchmark of the disaggregated P/D serving path (see run.py)."""
