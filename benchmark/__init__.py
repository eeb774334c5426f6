"""The benchmark: one cell, once, per process (``python benchmark/run.py``).

Everything a later PR may add is data found by name from ``BENCHMARK.json``:
``configs/<config>.json``, ``traffic/<traffic>.json``, ``metrics/<metric>.py``,
``limits/<cell>.json``, ``drivers/<driver>.py``, ``reference/<name>.py``.
The yardstick (traffic generation, trace reduction, peaks, FLOP and byte
counts, the plain references and the comparison behind ``correct``) lives
here and imports nothing of the program; only the drivers touch the program.
"""
