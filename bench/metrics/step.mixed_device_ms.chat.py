"""Device time of one mixed step, in ms: the median device duration of the
runs of ``jit_serve_mixed_step`` (the engine's padded prefill-and-decode
step, n_slots x budget rows) that lie inside the traced window, from the
``XLA Modules`` line of chip 0 (``bench/program_trace.py``). While prompts
prefill, decode lanes wait one mixed step per token. Moves
``ttft_p50_s``."""

import statistics

from bench import program_trace as PT


def read(ctx):
    red = PT.of(ctx)
    runs = red["modules"].get("jit_serve_mixed_step") if red else None
    return 1e3 * statistics.median(runs) if runs else None
