"""Chip benchmark of the mapping server: one cell per run, driven by the
data files under this directory (see ``run.py``)."""
