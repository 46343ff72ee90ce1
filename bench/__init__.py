"""The chip benchmark: one command runs one cell of ``BENCHMARK.json``.

Everything that decides a measurement lives here and nowhere in the
program: the data generator, the traffic runners, the trace reduction, the
peak table, the work counts and the plain references that decide
``correct``.  The program under test is imported from ``src/``.
"""
