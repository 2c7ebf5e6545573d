"""The on-chip benchmark of the MVGC serving system (see ``run.py``)."""
