"""The benchmark of the disaggregated serving cluster (see run.py)."""
