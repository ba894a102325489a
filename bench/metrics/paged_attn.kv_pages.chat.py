"""KV pages the fused decode attention (``paged_attn_*``) needs per decode
step in the traced window: the mean of the program's ``decode_pages`` over
the decode steps dispatched there, counted at dispatch from each decode
lane's cached context and the token the step writes, in whole pages. The
exact count beside ``paged_attn_roofline.chat``, whose bytes estimate the
same pages from token emit times. A program that records no
``decode_pages`` reads nothing. Moves ``ttft_p50_s``."""


def read(ctx):
    steps = ctx.traced_steps(("decode_step",))
    if not steps or any("decode_pages" not in e.args for e in steps):
        return None
    return sum(e.args["decode_pages"] for e in steps) / len(steps)
