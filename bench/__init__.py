"""The chip benchmark of the interconnect cost controller (see ``bench/run.py``)."""
