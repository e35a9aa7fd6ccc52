"""The on-chip benchmark of this repository (see ``bench/cell.py``)."""
