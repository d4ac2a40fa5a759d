"""Chip benchmark of the QPART served path (see PERF.md)."""
