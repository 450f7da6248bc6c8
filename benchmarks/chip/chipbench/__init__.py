"""On-chip benchmark of the OD-MoE serving path.

``run.py`` beside this package runs one cell of ``BENCHMARK.json`` once.
Everything a cell is made of is found by name: the model configuration
in ``configs/<config>.json``, the traffic mix in ``traffic/<mix>.json``
and each per-layer metric's reader in ``metrics/<metric>.py``.  The
modules here are the yardstick that later changes to the program cannot
move: traffic generation, the float32 reference and the comparison that
decides ``correct``, the trace reduction, the peaks table and the
operation and byte counts.
"""
