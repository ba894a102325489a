"""Reduce a JAX profiler trace (``.xplane.pb``) of a TPU to what the
per-layer metrics read.

What a v5e trace holds (read by hand first, from a chip run): one plane per
chip, ``/device:TPU:<n>``, whose line ``XLA Ops`` has one event per HLO
operation executed, named by its HLO text (``%mpmm_u8_i4_u8.3 =
f32[4096,8192]{...} custom-call(s8[4096,2048]{...} %x, s8[8192,1024]{...}
%w, ...)``: a Pallas kernel's instruction carries the kernel's own name and
every operand shape), and the line ``XLA Modules`` one event per program run.
Control-flow operations (``while``: the layer scan) span their bodies, so
they count toward busy time and not toward any kernel. Host threads are on
``/host:CPU``; the harness's ``TraceAnnotation`` spans are events of the
``python`` line. Times are nanoseconds from the start of the session.

The reduction gives:

  * ``window_s`` (the harness's clock from profiler start to stop) and
    ``busy_s``: the union of the intervals in which an operation ran,
    averaged over the chips traced;
  * ``kernels``: per kernel name (instance suffix stripped), device time and
    calls on chip 0; ``calls``: each call of a Pallas kernel with its output
    and operand shapes, for roofline counts;
  * ``gaps``: the idle intervals of chip 0, longest first, each named by the
    harness span the host was in at its midpoint.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
CONTAINERS = ("while", "conditional", "call")
#: harness annotations (bench/run.py) that can name an idle gap
HOST_SPANS = ("engine.step", "loadgen.submit", "loadgen.idle")
_HLO = re.compile(r"^%([A-Za-z_][\w\-]*?)(?:\.\d+)? = (.*)$")
_SHAPE = re.compile(r"([a-z]+\d*)\[([\d,]*)\]")


def parse_op(text: str) -> tuple[str, list[tuple[str, tuple[int, ...]]]]:
    """(kernel or op name, [(dtype, shape) of the result and each operand])
    of one ``XLA Ops`` event name."""
    m = _HLO.match(text)
    if not m:
        return text, []
    shapes = [(d, tuple(int(x) for x in s.split(",") if x))
              for d, s in _SHAPE.findall(m.group(2).split(", custom_call_target")[0])]
    return m.group(1), shapes


def _union(intervals):
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def reduce_file(path: str, w0: float, w1: float, pallas=("mpmm", "paged_")) -> dict:
    """Reduce one trace over [w0, w1] (seconds of its session clock). Calls
    of kernels whose names start with one of ``pallas`` keep their shapes."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    chips: dict[int, list] = {}
    host: list[tuple[float, float, str]] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                chips.setdefault(int(m.group(1)), []).extend(
                    (e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9, e.name)
                    for e in line.events)
            elif not m:
                host.extend((e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9, e.name)
                            for e in line.events if e.name in HOST_SPANS)
    if not chips:
        raise ValueError(f"no device operations in {path}")
    kernels: dict[str, dict] = {}
    calls: list = []
    busy, gaps = [], []
    for chip, evs in sorted(chips.items()):
        evs = [(max(a, w0), min(b, w1), n) for a, b, n in evs if b > w0 and a < w1]
        u = _union([(a, b) for a, b, _ in evs])
        busy.append(sum(b - a for a, b in u))
        if chip != min(chips):
            continue
        edges = [w0] + [x for iv in u for x in iv] + [w1]
        gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        for a, b, text in evs:
            name, shapes = parse_op(text)
            if name in CONTAINERS:
                continue
            d = kernels.setdefault(name, {"time_s": 0.0, "count": 0})
            d["time_s"] += b - a
            d["count"] += 1
            if name.startswith(pallas):
                calls.append((name, b - a, shapes))
    host.sort()
    starts = [a for a, _, _ in host]

    def host_at(t: float) -> str:
        i = bisect.bisect_right(starts, t) - 1
        best = "harness"
        while i >= 0 and t - host[i][0] < 60.0:
            if host[i][1] > t:
                best = host[i][2]
                break
            i -= 1
        return best

    named = sorted(((b - a, host_at((a + b) / 2)) for a, b in gaps), reverse=True)
    return {"window_s": w1 - w0, "busy_s": sum(busy) / len(busy), "chips": len(chips),
            "kernels": kernels, "calls": calls, "gaps": named}


def reduce_dir(trace_dir: str, w0: float, w1: float) -> dict:
    """Reduce the one trace the harness's profiler wrote to ``trace_dir``
    over [w0, w1] of its session clock."""
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise ValueError(f"expected one trace in {trace_dir}, found {files}")
    return reduce_file(files[0], w0, w1)


def breakdown(red: dict) -> dict:
    """The ten device operations that took most time, and the ten longest
    idle gaps by what the host was doing."""
    ops = sorted(((k, v["time_s"]) for k, v in red["kernels"].items()), key=lambda kv: -kv[1])
    return {"device_ops": [[n, t] for n, t in ops[:10]],
            "idle_gaps": [[n, t] for t, n in red["gaps"][:10]]}
