"""Share of the attention scores a mixed step computes that a real query
needs, in %. Every lane's n_slots x budget rows attend over its whole block
table, n_blocks x page_size keys (``models/attention.py``); a prefill chunk
of n tokens at offset o in its prompt needs n o + n (n + 1) / 2 causal
scores, and a decode lane with c cached tokens c + 1. Summed over the mixed
steps dispatched in the window, from the program's ``mixed_step`` args
(``decode_ctx_tokens``, ``decode_lanes``, ``budget``) and its
``prefill_chunk[i]`` spans (``offset``, ``tokens``; they share the step's
start). A program that records neither count reads nothing, and so does a
model with a sliding window (it needs fewer scores). Moves
``ttft_p50_s``."""


def read(ctx):
    eng = ctx.mix["engine"]
    if ctx.config.get("sliding_window"):
        return None
    keys = -(-eng["s_max"] // eng["page_size"]) * eng["page_size"]
    chunks: dict = {}
    for e in ctx.spans:
        if e.name.startswith("prefill_chunk["):
            chunks.setdefault(e.ts, []).append(e.args)
    need = computed = 0
    for e in ctx.window_steps(("mixed_step",)):
        if "decode_ctx_tokens" not in e.args:
            return None
        need += e.args["decode_ctx_tokens"] + e.args["decode_lanes"]
        for a in chunks.get(e.ts, []):
            if "offset" not in a:
                return None
            n, o = a["tokens"], a["offset"]
            need += n * o + n * (n + 1) // 2
        computed += eng["n_slots"] * e.args["budget"] * keys
    return 100.0 * need / computed if computed else None
