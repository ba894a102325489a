"""Run one cell of the benchmark once.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of ``workloads`` in ``BENCHMARK.json``: a configuration
(``bench/configs/<config>.json``, whose ``harness`` key names its model
family, ``bench/families/<family>/``) under a traffic mix
(``bench/traffic/<mix>.json``). The run loads the program and makes its
weights on the chip from the seed, warms up every shape the cell uses (all
of that is ``setup_s``), plays the traffic for ``--seconds``, checks what
the timed path produced against the plain reference (``bench/check.py``),
and prints one JSON object as the last line of standard output:

  * ``--trace 0``: the cell's end-to-end metrics, from the host clock;
  * ``--trace 1``: the cell's per-layer metrics, each read by its own file
    ``bench/metrics/<metric>.py`` from the program's spans, the requests'
    timestamps, the harness's clock and a profiler trace of the end of the
    window (``bench/trace_reduce.py``).

Without a TPU, or with fewer chips than the cell asks for, it exits non-zero
and prints no result. JAX's compilation cache is kept in
``$JAX_COMPILATION_CACHE_DIR`` when that is set, else in ``bench/.jax_cache``
of the checkout, so only a cell's first run in a checkout compiles.
"""

from __future__ import annotations

import time

T_PROC = time.perf_counter()  # set-up counts from here

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CACHE_DIR = os.path.join(ROOT, "bench", ".jax_cache")
GRACE_S = 60.0  # how long past the window a due request may still be served
TRACE_S = 4.0  # profiled span at the end of the window (--trace 1)


def die(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    sys.exit(2)


def read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: str = ROOT) -> dict:
    """The cell ``name`` with its configuration, traffic mix, limits and
    the metrics it reports, all found by name; its configuration's family
    must be there."""
    from bench import families

    bench = read_json(os.path.join(root, "BENCHMARK.json"))
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        die(f"no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    mix = read_json(os.path.join(root, "bench", "traffic", cell["traffic"] + ".json"))
    if mix["loop"] != "open":
        die(f"traffic {cell['traffic']!r}: only open-loop mixes are played")
    config = read_json(os.path.join(root, conf["file"]))
    try:
        families.get(config)
    except LookupError as e:
        die(str(e))

    def reports(m):
        return name in m.get("workloads", [name])

    return {
        "name": name, "chips": cell["chips"], "config": config, "mix": mix,
        "limits": read_json(os.path.join(root, "bench", "limits", name + ".json")),
        "end_to_end": [m for m in bench["end_to_end"] if reports(m)],
        "per_layer": [m for m in bench["per_layer"] if reports(m)],
    }


def enable_cache() -> str:
    import jax

    d = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return d


def find_chip(n: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        die(f"no TPU: JAX reports platform {devs[0].platform!r}")
    if len(devs) < n:
        die(f"the cell needs {n} TPU chips, JAX reports {len(devs)}")
    return devs[:n]


class CompileCounter:
    """Counts programs lowered (compiled, or fetched from the cache) while
    ``armed``: nothing should compile inside the measured window."""

    def __init__(self):
        from jax import monitoring

        self.armed, self.count = False, 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _dur, **_kw):
        if self.armed and name == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.count += 1


class Annotate:
    """Host spans in the profiler's own trace (``--trace 1`` only), so an
    idle gap on the device can be named by what the host was doing."""

    def __init__(self, on: bool):
        self.on = on

    def __call__(self, name: str):
        import contextlib

        import jax

        return jax.profiler.TraceAnnotation(name) if self.on else contextlib.nullcontext()


def play_open(eng, arrivals, seconds, on_token, note, prof):
    """Open loop: submit each request when it is due, step the engine in
    between, sleep only when it is idle. Returns (t0, t1, lag per request,
    handles)."""
    from bench import loadgen
    from bench import program as PG

    n, i, handles, lag = len(arrivals), 0, {}, {}
    t0 = time.perf_counter()
    t1 = t0 + seconds
    while True:
        now = time.perf_counter()
        prof.tick(now)
        while i < n and t0 + arrivals[i].due <= now:
            a = arrivals[i]
            with note("loadgen.submit"):
                handles[a.rid] = eng.submit(a.prompt, PG.greedy(a.max_new),
                                            rid=a.rid, on_token=on_token)
            lag[a.rid] = time.perf_counter() - (t0 + a.due)
            i += 1
        if now >= t1 and i >= n:
            return t0, t1, lag, handles
        with note("engine.step"):
            more = eng.step()
        if not more:
            with note("loadgen.idle"):
                loadgen.sleep_until(min(t1, t0 + arrivals[i].due) if i < n else t1)


def grace(eng, handles, deadline, note):
    """Past the window: serve until every due request has its first token
    (or the deadline), then stop. The late ones are late, not lost."""
    while time.perf_counter() < deadline and any(
            h.request.t_first == 0.0 for h in handles.values()):
        with note("engine.step"):
            if not eng.step():
                break


class Profiler:
    """Profiles the last ``TRACE_S`` seconds of the window into a temporary
    directory (``--trace 1``); ``tick`` is called from the players."""

    def __init__(self, on: bool):
        self.on, self.dir, self.t_start, self.t_stop = on, None, None, None
        self.t_ready = None
        self.start_at = None

    def arm(self, t1: float, seconds: float):
        self.start_at = t1 - min(TRACE_S, seconds / 2)

    def tick(self, now: float):
        if self.on and self.dir is None and self.start_at is not None and now >= self.start_at:
            import jax

            self.dir = tempfile.mkdtemp(prefix="bench_trace_")
            self.t_start = time.perf_counter()  # the session's clock starts here
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # Python calls would flood the host
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.t_ready = time.perf_counter()

    def stop(self):
        if self.on and self.dir is not None and self.t_stop is None:
            import jax

            self.t_stop = time.perf_counter()
            jax.profiler.stop_trace()


def serve(cell: dict, seed: int, seconds: float, trace: bool, *, fault=None) -> dict:
    """Set up, play the window, and gather every record the metrics and the
    check read. ``fault`` changes the timed path underneath: a planted fault
    (``bench/faults.py``, tests only) or the control's lower precision
    (``bench/control.py``)."""
    import jax
    import numpy as np

    from bench import families, loadgen
    from bench import program as PG

    c, mix = cell["config"], cell["mix"]
    eng_cfg = mix["engine"]
    note = Annotate(trace)
    counter = CompileCounter()
    if fault is not None:
        fault.install()
    acfg, pol = families.get(c).program.arch(c), PG.policy(c)
    phases = {"start": time.perf_counter() - T_PROC}  # interpreter, imports, chip

    def phase(name, t):
        phases[name] = time.perf_counter() - t

    t = time.perf_counter()
    with note("setup.weights"):
        params = PG.make_params(c, seed, acfg, pol)
        jax.block_until_ready(params)
    phase("weights", t)
    t = time.perf_counter()
    with note("setup.engine"):
        eng = PG.engine(params, acfg, pol, eng_cfg, traced=trace)
    phase("engine", t)
    t = time.perf_counter()
    # warm-up: one request through a mixed step, decode steps and the page
    # release compiles (or loads) every program the window runs
    rng = np.random.default_rng([seed, 1])
    with note("setup.warmup"):
        warm = eng.submit(rng.integers(0, c["vocab_size"], eng_cfg["mixed_budget"] + 3)
                          .astype(np.int32), PG.greedy(3), rid=-1)
        eng.drain()
    phase("warmup", t)
    if len(warm.request.out) != 3:
        die(f"warm-up request produced {len(warm.request.out)} tokens")

    emits: list = []

    def on_token(rid, _tok):
        emits.append((rid, time.perf_counter()))

    prof = Profiler(trace)
    rec = {"emits": emits}
    arrivals = loadgen.make_open(mix, seed, seconds, c["vocab_size"])
    setup_s = time.perf_counter() - T_PROC
    prof.arm(time.perf_counter() + seconds, seconds)
    counter.armed = True
    t0, t1, lag, handles = play_open(eng, arrivals, seconds, on_token, note, prof)
    counter.armed = False
    prof.stop()
    grace(eng, handles, t1 + GRACE_S, note)
    rec.update(due={a.rid: t0 + a.due for a in arrivals}, lag=lag,
               t_grace_end=time.perf_counter())
    rec.update(t0=t0, t1=t1, setup_s=setup_s, compiles_in_window=counter.count,
               prof=prof, setup_phases=phases)
    rec["spans"] = eng.trace.events() if eng.trace is not None else []
    rec["requests"] = {rid: h.request for rid, h in handles.items()}
    stats = jax.devices()[0].memory_stats() or {}
    rec["memory_peak_bytes"] = stats.get("peak_bytes_in_use")
    eng.close()
    del eng, params
    gc.collect()
    if fault is not None:
        fault.remove()
    return rec


def end_to_end(cell: dict, rec: dict) -> dict:
    """The cell's end-to-end metrics from the host clock."""
    from bench import loadgen

    t0, t1 = rec["t0"], rec["t1"]
    times: dict = {}
    for rid, t in rec["emits"]:
        times.setdefault(rid, []).append(t)
    gaps = [b - a for ts in times.values() for a, b in zip(ts, ts[1:]) if t0 <= b < t1]
    vals = {
        "setup_s": rec["setup_s"],
        "out_tokens_per_s": sum(t0 <= t < t1 for _, t in rec["emits"]) / (t1 - t0),
        "itl_p95_s": loadgen.percentile(gaps, 95),
    }
    vals["ttft_p50_s"] = loadgen.percentile(
        [(r.t_first if r.t_first else rec["t_grace_end"]) - rec["due"][rid]
         for rid, r in rec["requests"].items()], 50)
    return {m["name"]: {"value": vals[m["name"]], "unit": m["unit"]}
            for m in cell["end_to_end"]}


def counts(rec: dict) -> tuple[int, int]:
    """(attempted, failed). Attempted: every request due in the window.
    Failed: one that never got a first token, or one that finished short
    of its tokens."""
    reqs = rec["requests"].values()
    lost = sum(r.t_first == 0.0 for r in reqs)
    short = sum(r.status == "done" and len(r.out) != r.max_new for r in reqs)
    return len(rec["requests"]), lost + short


def load_metric(name: str):
    path = os.path.join(ROOT, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("bench_metric_" + name.replace(".", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def per_layer(cell: dict, rec: dict, dev) -> tuple[dict, dict, dict]:
    """(metrics, device additions, breakdown) of a traced run."""
    from bench import layers, trace_reduce

    prof = rec["prof"]
    red = trace_reduce.reduce_dir(prof.dir, prof.t_ready - prof.t_start, prof.t_stop - prof.t_start)
    ctx = layers.Context(cell, rec, red, dev)
    out = {}
    for m in cell["per_layer"]:
        v = load_metric(m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    devinfo = {"busy_s": red["busy_s"], "window_s": red["window_s"]}
    return out, devinfo, trace_reduce.breakdown(red)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    enable_cache()
    devs = find_chip(cell["chips"])
    from bench import check

    rec = serve(cell, args.seed, args.seconds, bool(args.trace))
    if args.trace:
        metrics, devextra, bd = per_layer(cell, rec, devs[0])
    else:
        metrics, devextra, bd = end_to_end(cell, rec), {}, None
    if rec["prof"].dir:
        shutil.rmtree(rec["prof"].dir, ignore_errors=True)
    attempted, failed = counts(rec)
    verdict = check.check(cell, rec, args.seed)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": rec["memory_peak_bytes"],
              **devextra}
    out = {"correct": verdict["correct"], "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device,
           "compiles_in_window": rec["compiles_in_window"],
           "setup_phases_s": rec["setup_phases"]}
    if bd is not None:
        out["breakdown"] = bd
    out["check"] = verdict["numbers"]
    for k, v in verdict["numbers"].items():
        print(f"check {k}: {v['value']!r} (limit {v['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
