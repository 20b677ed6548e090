"""Shared pieces of the benchmark: the registry of cells, configurations and
metrics, the chip peaks, the HLO collective parser, the trace reduction and
the plain reference. Nothing here imports the program under test."""
