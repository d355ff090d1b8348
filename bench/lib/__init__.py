"""Shared yardstick of the on-chip benchmark: data, graph, traffic,
reference, trace reduction, peaks and work counts. Nothing here imports
the program under test except `harness.py`, which drives it."""
