"""The benchmark: one harness (``bench/run.py``) driven by data files."""
