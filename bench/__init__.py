"""Chip benchmark of GWT training: harness, configurations, traffic mixes,
per-layer metric readers, the plain reference and the correctness check.
Run it with ``python3 bench/run.py`` (see ``bench/harness.py``)."""
