"""Chip benchmark of asynchronous R-FAST training (see ``run.py``)."""
