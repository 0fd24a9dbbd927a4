"""Benchmark of the certified-bracket path on the chip (see ``run.py``)."""
