"""Segment loop support: manifest, traffic, statistics, trace reduction,
peaks and cost functions. See PERF.md sections 2-4."""
