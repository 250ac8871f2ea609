"""Chip benchmark: cells, traffic, per-layer metric readers and the
yardstick that turns traces into numbers. Entry point: ``bench/run.py``."""
