"""Share of the rows a mixed step computes that carry a real token:
(prefill tokens + decode lanes) / (n_slots x budget), summed over every
mixed step dispatched in the window, in %, from the program's ``mixed_step``
span args. The rest are padding. Moves ``ttft_p50_s``."""


def read(ctx):
    steps = ctx.window_steps(("mixed_step",))
    if not steps:
        return None
    n_slots = ctx.mix["engine"]["n_slots"]
    real = sum(e.args["prefill_tokens"] + e.args["decode_lanes"] for e in steps)
    rows = sum(n_slots * e.args["budget"] for e in steps)
    return 100.0 * real / rows
