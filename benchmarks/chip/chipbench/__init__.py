"""The on-chip benchmark of the CP-LRC stripe store.

``benchmarks/chip/run.py`` runs one cell of ``BENCHMARK.json`` once. Each
configuration (``configs/<name>.json``), traffic mix (``traffic/<name>.json``)
and metric (``metrics/<name>.py``) is a file of its own, found by the name
``BENCHMARK.json`` gives it; this package is the general code that reads
them: the traffic generator, the plain reference and the comparison that
decides ``correct``, the trace reduction, the table of peaks and the
compile clock.
"""
