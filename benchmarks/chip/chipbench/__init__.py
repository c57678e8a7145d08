"""Compass on-chip benchmark harness (``benchmarks/chip/run.py``).

Everything a cell needs is found by name under ``benchmarks/chip/``:
``configs/<config>.json``, ``traffic/<mix>.json``,
``metrics/<metric>.py`` and ``reference/<family>.py``.  Adding a cell,
a mix, a metric or a reference is adding a file.
"""
