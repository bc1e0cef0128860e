"""The on-chip benchmark of governed paged serving.

Everything that belongs to one configuration, one traffic mix, one cell
or one metric lives in a file of its own, found by the name that
``BENCHMARK.json`` gives it (see ``spec.py``). The code in this package
is the yardstick: traffic generation, the served window, the reduction
from log timestamps, spans and traces to metrics, the plain reference
and the comparison that decides ``correct``.
"""
