"""What the program's own spans and named programs say about a run.

The serving engine names every program it jits (``jit_serve_mixed_step``,
``jit_serve_decode_step``, ...) and, with a ``Tracer`` attached, mirrors its
live spans into the profiler's trace as annotations on the host's
``python`` line: ``serve.step`` (one engine iteration), ``serve.admit``,
``mixed_step`` / ``decode_step`` (dispatch), ``retire`` (the wait for the
oldest step's tokens), ``serve.emit`` (token delivery) and ``host.gc`` (a
garbage collection of generation >= 1). Each annotation carries ``pc_ns``,
its start on the host's ``perf_counter`` clock in nanoseconds.

From a profiler trace (``reduce_file``):

  * ``modules``: for each program (the ``XLA Modules`` line of chip 0, its
    hash stripped), the device duration in seconds of every run that lies
    wholly inside the window, but the line's last, which the profiler's
    stop may have cut short;
  * ``clock``: the offset from the ``perf_counter`` clock to the session's
    clock, start minus ``pc_ns`` over every annotation that carries it, as
    its median and its spread (largest minus smallest), in ns.

Naming the device's idle gaps by these spans belongs to
``bench/trace_reduce.py`` (its ``HOST_SPANS``), where the result line's
breakdown names them; it is not repeated here.

From the program's ``Tracer`` events (``host_self_s``): the host's own time
in each engine iteration, its ``serve.step`` span less the ``retire`` spans
inside it. A program without these spans or names (an older one) yields
empty readings, never an error.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

from bench import trace_reduce as TR

MODULES_LINE = "XLA Modules"
#: spans the engine mirrors into a profile (``repro.serve.trace``)
PROGRAM_SPANS = ("serve.step", "serve.admit", "mixed_step", "decode_step",
                 "retire", "serve.emit", "host.gc")
_HASH = re.compile(r"\(\d+\)$")


def reduce_file(path: str, w0: float, w1: float) -> dict:
    """Reduce one trace over [w0, w1] (seconds of its session clock)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    chips: dict[int, dict] = {}
    offsets: list[float] = []
    for plane in pd.planes:
        m = TR.DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == MODULES_LINE:
                mods = chips.setdefault(int(m.group(1)), {})
                # the line's last run may be cut by the profiler's stop
                runs = sorted((e.start_ns * 1e-9, e.duration_ns * 1e-9, e.name)
                              for e in line.events)[:-1]
                for a, d, name in runs:
                    if w0 <= a and a + d <= w1:
                        mods.setdefault(_HASH.sub("", name), []).append(d)
            elif not m:
                for e in line.events:
                    if e.name in PROGRAM_SPANS:
                        pc = dict(e.stats).get("pc_ns")
                        if pc is not None:
                            offsets.append(e.start_ns - pc)
    clock = None
    if offsets:
        offsets.sort()
        clock = {"median_ns": offsets[len(offsets) // 2],
                 "spread_ns": offsets[-1] - offsets[0], "n": len(offsets)}
    return {"modules": chips[min(chips)] if chips else {}, "clock": clock}


def of(ctx) -> dict | None:
    """``reduce_file`` of a traced run's profile over the harness's window
    (as ``bench/run.py`` reduces it), computed once per run."""
    red = getattr(ctx, "program_trace", None)
    if red is None:
        prof = ctx.rec["prof"]
        if prof.dir is None:
            return None
        files = glob.glob(os.path.join(prof.dir, "**", "*.xplane.pb"), recursive=True)
        if len(files) != 1:
            return None
        red = reduce_file(files[0], prof.t_ready - prof.t_start, prof.t_stop - prof.t_start)
        ctx.program_trace = red
    return red


def host_self_s(spans, lo: float, hi: float) -> list[float]:
    """For each ``serve.step`` span that starts in [lo, hi) of the host
    clock, its duration less that of the ``retire`` spans inside it."""
    steps = sorted((e for e in spans if e.name == "serve.step" and lo <= e.ts < hi),
                   key=lambda e: e.ts)
    retires = sorted((e.ts, e.dur) for e in spans if e.name == "retire")
    starts = [t for t, _ in retires]
    out = []
    for s in steps:
        i = bisect.bisect_left(starts, s.ts)
        waited = 0.0
        while i < len(retires) and retires[i][0] < s.end:
            waited += retires[i][1]
            i += 1
        out.append(s.dur - waited)
    return out
