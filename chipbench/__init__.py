"""Chip benchmark of the serving path: cells, traffic, metrics and the reference.

Everything a cell needs is found by name under this directory:
``configs/<config>.json``, ``mixes/<mix>.json``, ``cells/<cell>.json``,
the architecture of a configuration's ``model_type`` in
``arch/<model_type>.py`` and one reader per per-layer metric in
``metrics/<metric>.py``. ``run.py`` is the one command; it holds no
per-cell or per-architecture branch.
"""
