"""Time the host spent in garbage collections of generation 1 and 2 in
the window, in ms: the sum of the program's ``host.gc`` spans that start in
it. A collection stalls the engine loop, and a request due then waits.
A program that records no ``serve.step`` spans records no collections
either, and reads nothing. Moves ``ttft_p50_s``."""


def read(ctx):
    t0, t1 = ctx.rec["t0"], ctx.rec["t1"]
    if not any(e.name == "serve.step" for e in ctx.spans):
        return None
    return 1e3 * sum(e.dur for e in ctx.spans if e.name == "host.gc" and t0 <= e.ts < t1)
