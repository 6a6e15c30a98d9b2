"""Chip benchmark of the certified TLFre / DPC engine.

One cell is one deployment (``configs/<config>.json``) under one traffic
mix (``traffic/<cell>.json``); each per-layer metric has a reader of its
own (``metrics/<metric>.py``).  ``python -m bench.run --workload <cell>
--seed <n> --seconds <s> --trace <0|1>`` runs one cell once on a TPU and
prints one JSON result line.  ``BENCHMARK.json`` at the root of the
repository is generated from these files (``python -m bench.catalog``).
"""
