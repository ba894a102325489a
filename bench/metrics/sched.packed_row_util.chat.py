"""Share of the rows the program's mixed steps compute that carry a token:
(prefill tokens + decode lanes) / rows, summed over every mixed step
dispatched in the window, in %, from the ``mixed_step`` span args (``rows``
is what the step computed). None where the steps record no ``rows``.
Moves ``ttft_p50_s``."""


def read(ctx):
    steps = [e for e in ctx.window_steps(("mixed_step",)) if "rows" in e.args]
    if not steps:
        return None
    real = sum(e.args["prefill_tokens"] + e.args["decode_lanes"] for e in steps)
    return 100.0 * real / sum(e.args["rows"] for e in steps)
