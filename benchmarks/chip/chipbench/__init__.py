"""Compass on-chip benchmark harness (``benchmarks/chip/run.py``).

Everything a cell needs is found by name under ``benchmarks/chip/``:
``configs/<config>.json``, ``traffic/<mix>.json``,
``metrics/<metric>.py``, ``reference/<family>.py`` and
``families/<family>.py`` (a model family's weight leaves, program keys and
work counts).  Adding a cell, a mix, a metric, a reference or a model
family is adding a file.
"""
