"""The benchmark's yardstick: loader, traffic, counts, peaks, the trace
reduction, the plain reference and the comparison that decides ``correct``."""
