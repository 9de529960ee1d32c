"""Benchmark of the lock laboratory and the serving engine on the chip (see run.py)."""
