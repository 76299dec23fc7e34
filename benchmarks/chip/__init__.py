"""Chip benchmark of the xDGP session: one cell per run, driven by data.

``BENCHMARK.json`` at the checkout's root lists the cells; each cell names a
configuration (``configs/<config>.json``) and a traffic mix
(``traffic/<traffic>.json``), and each per-layer metric has a reader of its
own (``metrics/<metric>.py``). ``run.py`` is the entry point.
"""
