"""The chip benchmark of the edge LM engine: ``python3 bench/run.py``.

Every configuration, traffic mix, correctness limit and metric is a file
of its own under this directory, found by the name ``BENCHMARK.json``
gives it (see ``cell.py``).
"""
