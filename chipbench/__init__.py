"""The on-chip benchmark of the serving plane (see ``run.py``)."""
