"""Shared machinery of the benchmark: manifest lookup, the device
gate and peaks, spans, the trace reduction, statistics, model text."""
