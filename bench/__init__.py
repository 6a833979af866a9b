"""On-chip benchmark of the similarity self-join (see ``run.py``)."""
