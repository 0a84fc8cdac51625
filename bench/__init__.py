"""Chip benchmark of the bridges engine (``python3 bench/run.py --help``).

Everything that belongs to one configuration, traffic mix or metric lives in
a file of its own, found by the name ``BENCHMARK.json`` gives it:
``configs/<config>.json`` names a generator in ``graphs/<generator>.py``,
``mixes/<traffic>.json`` names a driver in ``drivers/<driver>.py``, and each
metric is read by ``metrics/<metric>.py``.
"""
