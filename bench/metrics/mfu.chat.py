"""Model FLOP/s utilization of the whole step, in %: the operations of the
REAL tokens of the engine steps dispatched in the traced window (padding
rows do not count; the family's ``counts.py``, from shapes and token
counts) over the window times the chip's int8 peak (393 TOP/s on TPU v5e,
``bench/peaks.py``; the linears run int8 on the MXU). Moves
``ttft_p50_s``."""


def read(ctx):
    steps = ctx.traced_steps()
    if not steps:
        return None
    ops = sum(ctx.step_flops(e) for e in steps)
    return 100.0 * ops / ((ctx.prof_t1 - ctx.prof_t0) * ctx.peaks["int8_ops"])
