"""Share of its roofline the fused decode attention (``paged_attn_*``)
reaches, in %: the least time the chip could take to read the KV pages each
decode lane needs (whole pages inside its context and window, codes plus
scales, the family's ``counts.py``) over HBM bandwidth, summed over the
decode steps dispatched in the traced window, over the device time of the
paged_attn events in the trace. Decode steps share the chip with the
prefill of waiting requests and free the slots they wait for. Moves
``ttft_p50_s``."""


def read(ctx):
    steps = ctx.traced_steps(("decode_step",))
    dev = sum(v["time_s"] for k, v in ctx.trace["kernels"].items()
              if k.startswith("paged_attn"))
    if not steps or not dev:
        return None
    ps = ctx.mix["engine"]["page_size"]
    nbytes = sum(ctx.family.counts.paged_attn_bytes(ctx.config, n, ps)
                 for e in steps for n in ctx.decode_contexts(e.ts))
    return 100.0 * nbytes / ctx.peaks["hbm_bw"] / dev
