"""On-chip benchmark of the GGR serving path; run ``bench/run.py``."""
