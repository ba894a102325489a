"""Production serving driver: integer-path engine (packed weights +
quantized KV cache) with continuous batching over the request-lifecycle
API v1 (submit / drain; per-request sampling, priority admission).

On this container: PYTHONPATH=src python -m repro.launch.serve --smoke
"""

from __future__ import annotations

import argparse

import jax
import numpy as np

from repro import compile_cache, configs
from repro.core.policy import get_policy
from repro.models import model as M
from repro.serve import SamplingParams, ServeEngine, Tracer, write_exposition
from repro.serve.promexport import maybe_serve


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="h2o-danube-1.8b", choices=sorted(configs.ARCHS))
    ap.add_argument("--policy", default="mixed_paper")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--s-max", type=int, default=128)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--scheduler", default="fcfs",
                    choices=("fcfs", "spf", "bestfit", "priority"))
    ap.add_argument("--prefill", default="auto",
                    choices=("auto", "chunked", "stepwise"))
    ap.add_argument("--prefill-chunk", type=int, default=16)
    ap.add_argument("--cache", default="slot",
                    choices=("slot", "paged", "prefix"))
    ap.add_argument("--page-size", type=int, default=None)
    ap.add_argument("--mixed", action="store_true",
                    help="continuous batching: prefill chunks ride decode "
                         "steps under a token budget, steps dispatch "
                         "ahead-of-time (tokens bit-identical to the "
                         "serialized loop; needs chunked prefill)")
    ap.add_argument("--mixed-budget", type=int, default=None,
                    help="prefill tokens folded into each mixed step "
                         "(default: the prefill chunk size)")
    ap.add_argument("--spec", default="off",
                    choices=("off", "self4", "draft"),
                    help="speculative decoding: self4 drafts with the "
                         "target model at 4-bit weights (zero extra "
                         "weights, shared KV cache), draft uses a separate "
                         "small model; accepted streams are bit-identical "
                         "to --spec off")
    ap.add_argument("--spec-k", type=int, default=4, metavar="K",
                    help="drafted tokens per speculation round")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy decode")
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0,
                    help="base sampling seed; request i uses seed + i")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="record request/step spans; write a Chrome/Perfetto"
                         " trace_event JSON here (open at ui.perfetto.dev)")
    ap.add_argument("--trace-jsonl", default=None, metavar="OUT.jsonl",
                    help="also dump the raw event log, one JSON per line")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve metrics() as a Prometheus text exposition "
                         "on http://127.0.0.1:PORT/metrics (0 = ephemeral)")
    ap.add_argument("--metrics-dump", default=None, metavar="OUT.prom",
                    help="write the final Prometheus exposition to a file")
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()
    compile_cache.enable()

    cfg = configs.get_arch(args.arch)
    if args.smoke:
        cfg = configs.reduced(cfg)
    policy = get_policy(args.policy)
    params = M.init_params(jax.random.key(0), cfg, policy, mode="serve")
    tracer = Tracer() if (args.trace or args.trace_jsonl) else None
    eng = ServeEngine(params, cfg, policy, n_slots=args.slots, s_max=args.s_max,
                      scheduler=args.scheduler, prefill=args.prefill,
                      prefill_chunk=args.prefill_chunk, cache=args.cache,
                      page_size=args.page_size, mixed=args.mixed,
                      mixed_budget=args.mixed_budget,
                      spec=None if args.spec == "off" else args.spec,
                      spec_k=args.spec_k, trace=tracer)
    metrics_srv = maybe_serve(eng.metrics, args.metrics_port)
    if metrics_srv is not None:
        print(f"metrics: {metrics_srv.url}")
    rng = np.random.RandomState(0)
    handles = [
        eng.submit(rng.randint(1, cfg.vocab, size=4).astype(np.int32),
                   SamplingParams(temperature=args.temperature,
                                  top_k=args.top_k, top_p=args.top_p,
                                  seed=args.seed + i, max_new=args.max_new))
        for i in range(args.requests)]
    eng.drain()
    done = sum(len(h.result()) for h in handles)
    m = eng.metrics()
    print(f"served {len(handles)} requests / {done} tokens; "
          f"prefill={m['prefill_mode']} ({m['prefill_jit_calls']} jit calls); "
          f"ttft p50 {m['slo/ttft_p50_s'] * 1e3:.1f} ms / "
          f"p95 {m['slo/ttft_p95_s'] * 1e3:.1f} ms "
          f"(p50 queue {m['slo/ttft_queue_p50_s'] * 1e3:.1f} + "
          f"prefill {m['slo/ttft_prefill_p50_s'] * 1e3:.1f}); "
          f"tpot p95 {m['slo/tpot_p95_s'] * 1e3:.1f} ms; "
          f"tokens/s {m['tokens_per_s']:.1f}; "
          f"step ema {m['step_ema_s'] * 1e3:.1f} ms; "
          f"stragglers {m['stragglers']}")
    if m["spec/enabled"]:
        print(f"spec: policy={m['spec/policy']} k={m['spec/k']} "
              f"rounds={m['spec/rounds']} "
              f"accepted={m['spec/accepted']}/{m['spec/proposed']} "
              f"(rate={m['spec/acceptance_rate']:.2f}) "
              f"truncates={m['cache/truncates']}")
    if tracer is not None:
        tracer.check_request_spans(h.rid for h in handles)
        if args.trace:
            print(f"trace: {tracer.export_chrome(args.trace)} "
                  f"({m['trace/events_retained']} events, "
                  f"{m['trace/events_dropped']} dropped)")
        if args.trace_jsonl:
            print(f"trace jsonl: {tracer.export_jsonl(args.trace_jsonl)}")
    if args.metrics_dump:
        print(f"metrics exposition: "
              f"{write_exposition(args.metrics_dump, eng.metrics())}")
    if metrics_srv is not None:
        metrics_srv.close()


if __name__ == "__main__":
    main()
