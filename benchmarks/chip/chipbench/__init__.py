"""On-chip benchmark of the OD-MoE serving path.

``run.py`` beside this package runs one cell of ``BENCHMARK.json`` once.
Everything a cell is made of is found by name: the model configuration
in ``configs/<config>.json``, its architecture in
``arch/<model_type>.py``, the traffic mix in ``traffic/<mix>.json`` and
each per-layer metric's reader in ``metrics/<metric>.py``.  The modules
here, with the architecture plug-ins, are the yardstick that later
changes to the program cannot move: traffic generation, the comparison
that decides ``correct``, the trace reduction, the peaks table and the
grouped expert GEMM's operation and byte counts.  Each plug-in holds what
depends on the model: the mapping of its published configuration, its
weights, its float32 reference and its operation count.
"""
