"""On-chip benchmark of the exploration loop (see ``bench/run.py``)."""
