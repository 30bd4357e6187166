"""The on-chip benchmark of the STD result cache (see ``run.py``)."""
