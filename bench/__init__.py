"""Chip benchmark of the exscan library and its MoE consumer.

Everything here is data plus one harness (``run.py``): a cell of
``BENCHMARK.json`` names a configuration (``configs/<name>.json``), a
traffic mix (``traffic/<name>.json``, whose ``kind`` names a driver in
``drivers/``) and its correctness limits (``cells/<workload>.json``).
Per-layer metrics are one reader each in ``metrics/<name>.py``.
"""
