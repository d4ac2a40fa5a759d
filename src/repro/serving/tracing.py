"""Named host spans on the served path, written into a JAX profiler trace.

``span(name, **args)`` marks one phase of serving (planning, deploying
the quantized segment, the prefill, each decode step and the stages
inside them) as ``qpart.<name>`` on the profiler's host timeline, with
``args`` as the event's arguments. The profiler records it beside the
device programs the phase dispatches, so a trace can say which phase
enqueued which program and how long the host held the device idle.

No switch: outside a trace an annotation costs about a microsecond.
"""
from __future__ import annotations

import jax

PREFIX = "qpart."


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """Context manager marking ``qpart.<name>`` with ``args``."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **args)
