"""The on-chip benchmark of the served search path (see ``bench/run.py``)."""
