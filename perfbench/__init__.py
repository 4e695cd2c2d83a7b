"""Chip benchmark of the FPDT training path (see ``perfbench/run.py``)."""
