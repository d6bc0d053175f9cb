"""Chip benchmark harness: shared pieces that every cell uses.

Cells, configurations, traffic mixes and per-layer metrics are data or
small readers found by name (see ../README.md); this package holds the
yardstick itself: the load generator, the trace reduction, the peaks
table, the operation and byte counts, the plain reference and the
comparison that decides `correct`.
"""
