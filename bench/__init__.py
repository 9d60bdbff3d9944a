"""The chip benchmark of the edge LM engine: ``python3 bench/run.py``.

Every configuration, traffic mix, correctness limit and metric is a file
of its own under this directory, found by the name ``BENCHMARK.json``
gives it, and every model family (``families/<model_type>.py``) by the
``model_type`` its configuration names (see ``cell.py``).
"""
