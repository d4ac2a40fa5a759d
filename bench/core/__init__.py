"""Harness internals: specs, weights, traffic, program set-up, trace
reduction, peaks and the plain reference."""
