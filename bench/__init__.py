"""Serving benchmark of the FiGaRo join-factorization service on one TPU.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once. Everything a cell needs is found by
name: ``configs/<config>.py`` (a deployment), ``traffic/<mix>.json`` (a
traffic mix), ``limits/<cell>.json`` (the correctness limits),
``checks/<kind>.py`` (the comparison per serving kind) and
``metrics/<metric>.py`` (one reader per metric).
"""
