"""The SAFA benchmark: one cell of ``BENCHMARK.json`` per run of ``run.py``.

Everything that decides a number lives here, where a change to the
program cannot move it: traffic generation, the peak table, the work
functions, the trace reduction, the plain reference and its comparison.
"""
