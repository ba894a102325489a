"""Share of the traced window in which no operation ran on the chip, in %:
1 - (union of device op intervals) / window, from the profiler trace of the
end of the window (``bench/trace_reduce.py``). Moves ``itl_p95_s``."""


def read(ctx):
    t = ctx.trace
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
