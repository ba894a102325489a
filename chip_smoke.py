"""Smoke test of the serving path on one TPU chip, at the published widths
of internlm2-1.8b (24 layers, d_model 2048, 16 query / 8 KV heads of 128,
d_ff 8192, vocab 92544) under the ``mixed_paper`` precision policy, with
random weights from a seed.

    python chip_smoke.py                # one chip: serve + logits check
    python chip_smoke.py --four-chips   # four chips: train steps only

One chip, in one process:
  1. finds the chip (exits non-zero unless JAX reports a TPU);
  2. serves: ``ServeEngine(cache="paged", mixed=True)`` with the default
     fused decode attention takes 16 seeded requests of 128..1024 prompt
     tokens and 32 new tokens each, through chunked prefill, mixed steps and
     decode steps, and every request must finish with all its tokens;
  3. checks that every kernel cell the engine dispatched is a Pallas one;
  4. runs one prefill chunk (``model.mixed_step``) and one decode step
     (``model.decode_step``) with ``impl="pallas"``, in which every
     transformer block, the LM head (the logits) and every kernel is also
     computed with ``impl="jnp"`` on the same inputs, and bounds each
     difference.

Four chips (``--four-chips``): a few training steps of the same model at
sequence length 4096 on a 2x2 (data x model) mesh, set up by the training
launcher (``launch.train.setup``: state created sharded), compared on the
first step's loss with the same batch on a 4x1 mesh.

The last line of standard output is one JSON object naming the device;
every earlier line is informational. Any failed phase exits non-zero.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

ARCH = "internlm2-1.8b"
POLICY = "mixed_paper"
SEED = 0

# serving traffic: prompts long enough that chunked prefill and mixed steps
# run, few enough shapes that the run stays at a few minutes
N_REQUESTS = 16
PROMPT_MIN, PROMPT_MAX = 128, 1024
MAX_NEW = 32
N_SLOTS = 8
CHUNK = 256  # prefill chunk = mixed-step token budget
S_MAX = 1088  # PROMPT_MAX + MAX_NEW, rounded up to whole 16-row pages

# Pallas vs jnp, on the inputs the Pallas run produces: one prefill chunk
# (``model.mixed_step``) and one fused decode step (``model.decode_step``)
# run with impl="pallas", and a function of that run is also computed with
# impl="jnp" on the same arguments; the Pallas result is carried on. Run
# free, the two implementations cannot be compared tightly: their float glue
# (norms, softmax, residual adds) is compiled into two XLA programs that may
# round differently, and 24 layers of 8-bit activations amplify one such
# flip (13.5% of the logits' range on the prefill chunk, on a TPU v5e).
# Paired this way, nothing builds up over layers.
#
# Per kernel (``kernels.ops``): the page movers copy bytes, so they must
# agree bit for bit. The integer matmul accumulates exactly in int32 on both
# sides; only its f32 scaling by eps_x * eps_w may round once differently
# (XLA may associate the two scalars with the product either way): a few f32
# ulps of the largest output, 2^-21 of it, where one unit of a wrong int32
# accumulator is far more. The fused decode attention is float math: the
# twin mirrors its page-blocked softmax, but either side may round its f32
# dot operands to bf16 (XLA's default TPU precision), which moves a score by
# up to 2^-8 of itself; the context, a convex combination of value rows,
# then moves by a few such steps: 1/64 of its range.
KERNEL_REL_BOUNDS = {"mpmm": 2**-21, "paged_scatter": 0.0,
                     "paged_gather": 0.0, "paged_attn": 1 / 64}
#
# Per transformer block (``model._block_apply``: every layer of both steps,
# teacher-forced on the Pallas hidden state and cache) and for the LM head
# (``model.linear_apply`` on the last hidden state: the logits). Given equal
# kernel outputs, the two sides differ only where the glue rounds
# differently: an f32 ulp that crosses a bf16 rounding point, which may in
# turn move one 8-bit activation code by one step (1/127 of its token's
# range) before the next matmul. A block's output is a bf16 residual sum, so
# one changed rounding moves an element by one bf16 ulp, at most 2^-7 of
# the output's range; a few such steps, plus the matmuls' response to a few
# moved codes, stay under 2^-5 of the range. The logits come from one
# matmul of the same hidden state with int32 accumulation: only the
# activation codes can differ, so 2/127 of the range (two steps of the
# largest code) bounds them. A wrong layout, scale or mask moves a block's
# output or the logits by a sizeable share of their range.
MODEL_REL_BOUNDS = {"_block_apply": 2**-5, "linear_apply": 2 / 127}

# four-chip training phase
TRAIN_SEQ = 4096
TRAIN_BATCH = 4
TRAIN_STEPS = 4
# The first step's loss on the 2x2 mesh and on the 4x1 mesh is the same sum
# reduced in a different order across devices (bf16 params, f32 loss), so
# the two agree to f32 rounding of a mean over 16k tokens; 1e-3 relative is
# that rounding with a wide margin, and far below what a wrong sharding
# (a dropped or doubled shard) would change.
LOSS_REL_TOL = 1e-3


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def find_chip(n: int):
    """The JAX devices, if they are ``n`` or more TPU chips; else exit."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"no TPU: JAX reports platform {devs[0].platform!r}")
    if len(devs) < n:
        fail(f"needs {n} TPU chips, JAX reports {len(devs)}")
    print(f"device: {devs[0].device_kind} x {len(devs)}", flush=True)
    return devs


def peak_hbm(dev) -> str:
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 2**30:.2f} GiB"


# ---------------------------------------------------------------- serving


def make_prompts(vocab: int):
    import numpy as np

    rng = np.random.default_rng(SEED)
    lens = rng.integers(PROMPT_MIN, PROMPT_MAX + 1, N_REQUESTS)
    return [rng.integers(0, vocab, n).astype(np.int32) for n in lens]


def serve_phase(params, cfg, policy) -> None:
    from repro.kernels import dispatch
    from repro.serve import SamplingParams, ServeEngine

    engine = ServeEngine(params, cfg, policy, n_slots=N_SLOTS, s_max=S_MAX,
                         cache="paged", mixed=True, prefill_chunk=CHUNK)
    sp = SamplingParams(max_new=MAX_NEW)

    # warm-up: one request compiles the mixed step, the decode step and the
    # page release; the traffic below then runs without compiling
    t0 = time.perf_counter()
    warm = engine.submit(make_prompts(cfg.vocab)[0][:PROMPT_MIN], sp)
    engine.drain()
    compile_s = time.perf_counter() - t0
    if len(warm.request.out) != MAX_NEW:
        fail(f"warm-up request produced {len(warm.request.out)} tokens")

    handles = [engine.submit(p, sp) for p in make_prompts(cfg.vocab)]
    t0 = time.perf_counter()
    engine.drain()
    run_s = time.perf_counter() - t0
    short = [(h.request.rid, len(h.request.out)) for h in handles
             if h.request.status != "done" or len(h.request.out) != MAX_NEW]
    if short:
        fail(f"requests without their full {MAX_NEW} tokens: {short}")
    prompt_tokens = sum(len(h.request.prompt) for h in handles)
    print(f"serve: {len(handles)} requests, {prompt_tokens} prompt tokens, "
          f"{len(handles) * MAX_NEW} tokens generated; "
          f"warm-up (mostly compile) {compile_s:.1f} s, traffic {run_s:.1f} s",
          flush=True)

    cells = engine.kernel_stats()
    jnp_cells = sorted(c for c in cells if c.endswith("@jnp"))
    if jnp_cells:
        fail(f"jnp kernel cells dispatched on the chip: {jnp_cells}")
    if dispatch.resolve_impl("auto") != "pallas":
        fail("impl='auto' does not resolve to the Pallas kernels")
    print("pallas cells: " + ", ".join(sorted(cells)), flush=True)


def logits_check(params, cfg, policy) -> None:
    """Pallas vs jnp on the inputs of one Pallas prefill chunk and decode
    step: each transformer block and the LM head, then each kernel."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops
    from repro.models import model as M

    B, W, ps = 2, CHUNK, 16
    nb = (W + ps) // ps  # pages for the chunk plus the decoded token
    rng = np.random.default_rng(SEED + 1)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (B, W)), jnp.int32)
    nxt = jnp.asarray(rng.integers(0, cfg.vocab, (B, 1)), jnp.int32)
    bt = jnp.arange(1, B * nb + 1, dtype=jnp.int32).reshape(B, nb)
    zeros = jnp.zeros((B,), jnp.int32)
    pos = jnp.full((B,), W, jnp.int32)

    def run():
        prefill = jax.jit(lambda p, t, c: M.mixed_step(
            p, t, zeros, pos, c, cfg, policy, impl="pallas", block_tables=bt,
            page_size=ps))
        decode = jax.jit(lambda p, t, c: M.decode_step(
            p, t, pos, c, cfg, policy, impl="pallas", block_tables=bt,
            fused_attn=True))
        caches = M.init_paged_cache(cfg, policy, B * nb + 1, ps)
        lp, caches = prefill(params, toks, caches)
        ld, _ = decode(params, nxt, caches)
        for name, lg in (("prefill chunk", lp), ("decode step", ld)):
            if not np.isfinite(np.asarray(lg, np.float32)).all():
                fail(f"{name}: non-finite logits")
        jax.effects_barrier()

    def paired(module, bounds: dict) -> None:
        """Runs both steps with the functions of ``module`` named in
        ``bounds`` computed Pallas and jnp on the same arguments; checks each
        call's largest difference against the bound x that call's jnp
        range."""
        worst: dict = {}  # name -> (diff / range, diff, range) of its worst call

        def record(name, diff, scale):
            diff, scale = float(diff), float(scale)
            rel = diff / scale if scale else (math.inf if diff else 0.0)
            if name not in worst or rel > worst[name][0]:
                worst[name] = (rel, diff, scale)

        def pair(name, fn):
            def call(*args, **kw):
                kw.pop("impl", None)
                out = fn(*args, impl="pallas", **kw)
                twin = fn(*args, impl="jnp", **kw)
                a, b = (out[0], twin[0]) if isinstance(out, tuple) else (out, twin)
                a, b = a.astype(jnp.float32), b.astype(jnp.float32)
                jax.debug.callback(functools.partial(record, name),
                                   jnp.max(jnp.abs(a - b)),
                                   jnp.max(jnp.abs(b)))
                return out
            return call

        real = {attr: getattr(module, attr) for attr in bounds}
        for attr, fn in real.items():
            setattr(module, attr, pair(attr, fn))
        try:
            run()
        finally:
            for attr, fn in real.items():
                setattr(module, attr, fn)
        for attr, bound in bounds.items():
            name = f"{module.__name__.rsplit('.', 1)[-1]}.{attr}"
            if attr not in worst:
                fail(f"{name} was not called by the prefill chunk or decode step")
            rel, diff, scale = worst[attr]
            print(f"{name}: worst call max|pallas - jnp| = {diff:.6g}, "
                  f"max|jnp| = {scale:.6g}: {rel:.6g} of the range, bound "
                  f"{bound:.6g}", flush=True)
            if rel > bound:
                fail(f"{name}: Pallas and jnp differ by {diff:.6g} "
                     f"> {bound:.4g} x {scale:.6g}")

    paired(M, MODEL_REL_BOUNDS)
    paired(ops, KERNEL_REL_BOUNDS)


def one_chip() -> dict:
    import jax

    from repro import configs
    from repro.core.policy import get_policy
    from repro.models import model as M

    dev = find_chip(1)[0]
    cfg, policy = configs.get_arch(ARCH), get_policy(POLICY)
    params = jax.jit(lambda k: M.init_params(k, cfg, policy, mode="serve"))(
        jax.random.key(SEED))
    serve_phase(params, cfg, policy)
    logits_check(params, cfg, policy)
    print(f"peak HBM: {peak_hbm(dev)}", flush=True)
    return {"platform": dev.platform, "kind": dev.device_kind, "count": 1}


# --------------------------------------------------------------- training


def four_chips() -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import configs
    from repro.configs.shapes import ShapeCfg
    from repro.core.policy import get_policy
    from repro.launch import train as TL
    from repro.train import optimizer as opt
    from repro.train import step as T

    devs = find_chip(4)[:4]
    cfg, policy = configs.get_arch(ARCH), get_policy(POLICY)
    # warmup 1: every step of the few takes the full learning rate
    tcfg = T.TrainCfg(opt=opt.OptCfg(warmup_steps=1, total_steps=TRAIN_STEPS))
    shape = ShapeCfg("chip_smoke", TRAIN_SEQ, TRAIN_BATCH, "train")
    rng = np.random.default_rng(SEED)
    # one batch, every step: the loss must fall as the model fits it
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab, (TRAIN_BATCH, TRAIN_SEQ)), jnp.int32)}

    def setup(data: int, model: int):
        # the launcher's own setup: state created sharded, its jitted step
        ts = TL.setup(cfg, policy, tcfg, shape, TL.make_mesh(data, model))
        return ts.init, ts.step, jax.device_put(batch, ts.batch_shardings)

    t0 = time.perf_counter()
    init, step, b = setup(2, 2)
    state = init()
    losses = []
    for i in range(TRAIN_STEPS):
        state, metrics = step(state, b)
        losses.append(float(metrics["loss"]))
        if i == 0:
            first_s = time.perf_counter() - t0
    run_s = time.perf_counter() - t0 - first_s
    print(f"train 2x2: losses {losses}; first step (with compile) "
          f"{first_s:.1f} s, next {TRAIN_STEPS - 1} steps {run_s:.1f} s",
          flush=True)
    spread = {len({s.device for s in leaf.addressable_shards})
              for leaf in jax.tree.leaves(state["params"])}
    if spread != {4}:
        fail(f"parameter leaves span {sorted(spread)} devices, not 4")
    for d in devs:
        print(f"  {d}: peak HBM {peak_hbm(d)}", flush=True)
    if not all(np.isfinite(losses)):
        fail(f"non-finite loss: {losses}")
    if not losses[-1] < losses[0]:
        fail(f"loss did not go down: {losses}")
    del state

    init, step, b = setup(4, 1)
    _, metrics = step(init(), b)
    ref = float(metrics["loss"])
    rel = abs(ref - losses[0]) / abs(ref)
    print(f"train 4x1: first-step loss {ref}; relative difference to 2x2 "
          f"{rel:.3g} (tolerance {LOSS_REL_TOL})", flush=True)
    if rel > LOSS_REL_TOL:
        fail(f"first-step loss 2x2 {losses[0]} vs 4x1 {ref}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip training phase")
    args = ap.parse_args()
    from repro import compile_cache

    print(f"compile cache: {compile_cache.enable()}", flush=True)
    device = four_chips() if args.four_chips else one_chip()
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
