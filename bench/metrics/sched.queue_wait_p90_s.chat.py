"""Time a request waits for a slot: the 90th percentile of (admitted -
submitted) over every request due in the window, in s, from the requests'
own timestamps (``t_submit``, ``t_admit``, stamped by the program). A
request never admitted counts until the end of the run. Moves
``ttft_p50_s``."""

from bench.loadgen import percentile


def read(ctx):
    if "due" not in ctx.rec:
        return None
    end = ctx.rec["t_grace_end"]
    waits = [(r.t_admit or end) - r.t_submit for r in ctx.rec["requests"].values()]
    return percentile(waits, 90) if waits else None
