"""The chip benchmark of the SuperNIC reproduction: cells, traffic mixes,
metric readers, the reference and the trace reduction (see ``run.py``)."""
