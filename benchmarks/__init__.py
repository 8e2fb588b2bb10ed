"""The chip benchmark of geomesa-tpu (``python benchmarks/run.py``)."""
