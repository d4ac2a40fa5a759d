"""expert_ms_per_step — model step (``models/moe.py``
``moe_routed_apply`` through ``kernels/moe_gmm.py``: the routed-expert
layers' grouped kernel inside each decode step).

Device milliseconds of the grouped expert kernel's calls (``moe_gmm``,
told apart as ``moe_roofline`` says) that run inside the device programs
the program's ``qpart.step`` spans dispatched, divided by the number of
steps (0 where no step's token reached a held expert). Moves
``itl_p95_ms``. None where the trace holds no such span or no call of
the kernel at all (a program without it).
"""
from __future__ import annotations

from bench.core import program_trace
from bench.core.spec import metric_reader
from bench.core.trace import device_trace


def read(view):
    tv = device_trace(view)
    pv = program_trace.view_of(view)
    is_kernel = metric_reader("moe_roofline").is_kernel
    if tv is None or pv is None or not pv.of("step") \
            or tv.op_ns(is_kernel) == 0:
        return None
    runs = [(m.start, m.start + m.dur) for m in pv.modules
            if m.path and m.path[0] == "step"]
    ns = tv.op_ns(is_kernel, runs) if runs else 0.0
    return ns / 1e6 / len(pv.of("step"))
