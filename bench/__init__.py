"""On-chip benchmark of the Chameleon training runtime.

Everything the benchmark measures with lives here, apart from the program:
the cells' configurations and traffic (data files found by name), the
token generator, the peaks table, the model-FLOP count, the reduction
from a profiler trace to metrics, the plain float32 reference and the
comparison that decides ``correct``.  Entry point: ``python3 bench/run.py``.
"""
