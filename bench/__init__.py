"""On-chip benchmark of the DP-LASSO Frank-Wolfe solver.

``bench/run.py`` is the command; ``BENCHMARK.json`` at the repository root
names the cells.  Everything that measures lives here and imports nothing
from the program except the entry points under test (``solve``,
``FitService``, ``as_padded``) and the program's telemetry spans.
"""
