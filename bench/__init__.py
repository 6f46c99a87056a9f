"""On-chip benchmark of the triangle-survey system (see ``run.py``)."""
