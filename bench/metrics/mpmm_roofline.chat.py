"""Share of its roofline the ``mpmm_*`` kernels reach, in %: for every mpmm
call in the traced window, the least time the chip could take, the larger
of operations over the int8 peak and bytes over HBM bandwidth, from the
call's own shapes in the trace (``bench/flops.py``; padded rows count, they
are the kernel's work), summed and divided by the calls' device time.
Moves ``ttft_p50_s``."""

from bench import flops as F


def read(ctx):
    return F.mpmm_roofline(ctx.trace["calls"], ctx.peaks)
