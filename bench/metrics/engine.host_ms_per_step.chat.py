"""The host's own work per engine iteration, in ms: the median over the
program's ``serve.step`` spans that start in the window of the span's
duration less its ``retire`` spans (the wait for the oldest step's tokens):
admission, dispatch, token delivery, garbage collection
(``bench/program_trace.py``). Hidden under the device's step today, it
sets the pace once the step is shorter. Moves ``itl_p95_s``."""

import statistics

from bench import program_trace as PT


def read(ctx):
    own = PT.host_self_s(ctx.spans, ctx.rec["t0"], ctx.rec["t1"])
    return 1e3 * statistics.median(own) if own else None
