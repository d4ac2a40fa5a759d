"""Serial open-loop driver: the program's executed path today.

For each request in arrival order: wait for its due time if the server is
idle, plan it with ``QPARTServer.serve`` and stream it with
``Deployment.generate``, timestamping every ``stream_cb`` call. One
request at a time: a request due while another is in service waits, and
its time to first token counts that wait. Requests due in the window
that have not finished ``DRAIN_S`` seconds after it closes are failed.
"""
from __future__ import annotations

import time

from bench.core.program import plan_of
from bench.core.run_view import Record

DRAIN_S = 30.0


def _sleep_until(t: float, clock) -> None:
    while True:
        left = t - clock()
        if left <= 0:
            return
        time.sleep(min(left, 0.05) if left > 0.002 else 0)


def run(system, requests, seconds: float, tracer, clock=time.perf_counter):
    """-> (window start on ``clock``, [Record])."""
    records = []
    t0 = clock()
    drain_end = t0 + seconds + DRAIN_S
    with tracer.span("window"):
        for req in requests:
            rec = Record(req.index, len(req.prompt), req.max_new_tokens,
                         due=t0 + req.due_s)
            records.append(rec)
            if clock() < rec.due:
                with tracer.span("arrival_wait", request=req.index):
                    _sleep_until(rec.due, clock)
                rec.late_s = clock() - rec.due
            if clock() >= drain_end:
                continue
            with tracer.span("serve", request=req.index):
                rec.serve_start = clock()
                dep = system.serve(req.context, req.max_new_tokens)
                rec.serve_end = clock()
            rec.plan = plan_of(dep)

            def cb(i, tok, rec=rec):
                rec.token_times.append(clock())
                rec.tokens.append(int(tok[0]))
                if i + 1 < rec.n_new:
                    tracer.switch("decode", request=rec.index, step=i + 1)
                else:
                    tracer.switch(None)

            with tracer.span("generate", request=req.index):
                rec.gen_start = clock()
                tracer.switch("prefill", request=req.index)
                try:
                    dep.generate(req.prompt[None, :], req.max_new_tokens,
                                 max_len=system.max_len, stream_cb=cb)
                finally:
                    tracer.switch(None)
            rec.done = rec.token_times[-1] <= drain_end
    return t0, records
