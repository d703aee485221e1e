"""On-chip benchmark of the PaPaS reproduction (see ``run.py``)."""
