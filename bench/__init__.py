"""On-chip benchmark of the ADE-HGNN serving system (see ``run.py``)."""
