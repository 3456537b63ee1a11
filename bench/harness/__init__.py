"""The chip benchmark's yardstick: cell resolution, traffic generation, the
serving drive loop, trace reduction, metric arithmetic and the output check.

Everything that belongs to one configuration, traffic mix, cell or per-layer
metric lives in a file of its own under ``bench/`` and is found by the name
``BENCHMARK.json`` gives it (see :mod:`harness.spec`).
"""
