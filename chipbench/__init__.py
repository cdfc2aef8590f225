"""The chip benchmark of the query service (see ``run.py``)."""
