"""The benchmark's own machinery: loading cells by name, the load
generators, timing, the trace reduction and the result line.

Nothing here is imported by the program under test, and nothing here
changes when the program changes: a cell, a traffic mix, a configuration
or a per-layer metric is added as a file that these modules find by the
name ``BENCHMARK.json`` gives it.
"""
