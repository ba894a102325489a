"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell and
extract roofline raw material (cost_analysis, memory_analysis, HLO
collective bytes) without touching real hardware.

Usage:
  python -m repro.launch.dryrun --arch internlm2-1.8b --shape train_4k
  python -m repro.launch.dryrun --arch ... --shape ... --multi-pod
  python -m repro.launch.dryrun --all            # every cell, subprocess each
Artifacts: experiments/dryrun/<arch>__<shape>__<mesh>.json (incremental).
"""

# The dry-run is a projection on the CPU: 512 placeholder host devices, CPU
# cost_analysis (until ROADMAP A2 moves it onto a described TPU topology).
# These lines MUST run before any other import (jax locks platform and
# device count on first init). Pinning the CPU also keeps this process and
# the --all children it starts off any attached chip. Set here only — never
# globally (tests/benches see 1 device).
import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

import argparse  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro import configs  # noqa: E402
from repro import runtime_flags as RF  # noqa: E402
from repro.configs.shapes import SHAPES, input_specs, shape_applicable  # noqa: E402
from repro.core.policy import get_policy  # noqa: E402
from repro.launch import mesh as MX  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.train import optimizer as opt  # noqa: E402
from repro.train import step as T  # noqa: E402

# FLOP/collective accounting strategy (EXPERIMENTS.md Sec. Dry-run):
# XLA cost_analysis counts a while-loop body ONCE (verified experimentally),
# so the rolled full-depth compile under-reports FLOPs/collective bytes by
# the scan trip counts. Unrolling the full model is compile-prohibitive on
# one CPU core. We therefore compile each cell THREE times:
#   1. full config, scans ROLLED  -> the compile proof + memory_analysis
#      (exactly the program a real run executes);
#   2+3. reduced-depth variants (e.g. L=2, L=4), scans UNROLLED -> exact
#      per-layer cost/collectives; linear fit in L extrapolates to true depth
#      (cost(L) = base + per_layer * L holds exactly for homogeneous stacks).
RF.FLAGS["ssm_chunk"] = 1024  # bound unrolled SSM chunk count (trace-only)

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "experiments", "dryrun")

_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8, "c64": 8,
    "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s4": 0.5, "u4": 0.5,
}

_COLL_RE = re.compile(
    r"=\s*(\(?[^()]*?\)?)\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(", re.M)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")


def hlo_collective_bytes(hlo: str) -> dict:
    """Sum result-shape bytes per collective op kind from partitioned HLO.

    The compiled module is the per-device program, so shapes are shard-local:
    result bytes ~= bytes received per device per op execution. '-done' ops
    are skipped (the '-start' carries the shape) to avoid double counting.
    """
    out: dict[str, dict] = {}
    for m in _COLL_RE.finditer(hlo):
        if "-done(" in m.group(0):
            continue
        shapes, op = m.group(1), m.group(2)
        nbytes = 0.0
        for dt, dims in _SHAPE_RE.findall(shapes):
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * _DTYPE_BYTES[dt]
        rec = out.setdefault(op, {"count": 0, "bytes": 0.0})
        rec["count"] += 1
        rec["bytes"] += nbytes
    return out


def param_counts(params_struct, cfg) -> tuple[float, float]:
    """(total, active) parameter counts from the train-mode param structure.
    Expert leaves (L, E, d_out, d_in) count top_k/E toward 'active'."""
    total = active = 0.0
    for path, leaf in jax.tree_util.tree_flatten_with_path(params_struct)[0]:
        names = [str(getattr(p, "key", "")) for p in path]
        if not names or names[-1] not in ("w", "table"):
            continue
        n = float(np.prod(leaf.shape))
        total += n
        if leaf.ndim == 4 and cfg.n_experts:  # stacked experts
            active += n * cfg.top_k / cfg.n_experts
        else:
            active += n
    return total, active


def _mem_fields(mem) -> dict:
    out = {}
    for f in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"):
        try:
            out[f] = int(getattr(mem, f))
        except Exception:
            pass
    return out


def _scalar_costs(cost) -> dict:
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    return {k: float(v) for k, v in cost.items()
            if isinstance(v, (int, float)) and np.isfinite(float(v))}


import dataclasses  # noqa: E402


def variant_layers(cfg) -> tuple[int, int]:
    """Two reduced depths for the per-layer cost fit (structure-preserving).
    Costs are exact (not noisy), so a 1-layer delta gives the per-layer
    slope exactly; hybrid needs a full shared-attn period in the delta."""
    if cfg.family == "mla_moe":  # keep the dense prefix, vary MoE depth
        return (cfg.dense_layers + 1, cfg.dense_layers + 2)
    if cfg.family == "hybrid":
        # delta = one shared-attn period: L=a -> 1 app, L=3a -> 2 apps
        h = cfg.attn_every // 2
        return (h, h + cfg.attn_every)
    return (1, 2)


def with_layers(cfg, L: int):
    upd = {"n_layers": L}
    if cfg.family == "encdec":
        upd["enc_layers"] = L
    return dataclasses.replace(cfg, **upd)


def lower_cell(cfg, shape, env, policy, *, microbatches: int = 1,
               remat: bool = True, remat_policy: str = "full",
               zero3_params: bool = True):
    """Lower one (cfg x shape) under the given mesh env. Returns `lowered`.
    ``zero3_params=True`` keeps the naive fsdp-params baseline; False =
    ZeRO-2 (hillclimb)."""
    key = jax.random.key(0)

    if shape.kind == "train":
        tcfg = T.TrainCfg(remat=remat, microbatches=microbatches,
                          remat_policy=remat_policy)
        state_struct = jax.eval_shape(
            lambda: T.init_train_state(key, cfg, policy, tcfg))
        # ZeRO-2 by default: params TP-only (GSPMD replicated-compute hazard
        # on fsdp'd params — Perf iteration 1), optimizer moments dp-sharded.
        pspecs = MX.param_specs(state_struct["params"], env,
                                fsdp=env.fsdp and zero3_params)
        mspecs = MX.param_specs(state_struct["params"], env, fsdp=True)
        state_specs = {
            "params": pspecs,
            "opt": {"m": mspecs, "v": mspecs, "step": P()},
        }
        bspecs = MX.batch_specs(cfg, shape, env)
        batch_struct = input_specs(cfg, shape)
        step = T.make_train_step(cfg, policy, tcfg, impl="jnp")
        out_struct = jax.eval_shape(step, state_struct, batch_struct)
        out_specs = (state_specs, jax.tree.map(lambda _: P(), out_struct[1]))
        jitted = jax.jit(
            step,
            in_shardings=(MX.tree_shardings(state_specs, env),
                          MX.tree_shardings(bspecs, env)),
            out_shardings=(MX.tree_shardings(out_specs[0], env),
                           MX.tree_shardings(out_specs[1], env)),
            donate_argnums=(0,),
        )
        lowered = jitted.lower(state_struct, batch_struct)

    elif shape.kind == "prefill":
        params_struct = jax.eval_shape(
            lambda: M.init_params(key, cfg, policy, mode="serve"))
        pspecs = MX.param_specs(params_struct, env,
                                fsdp=env.fsdp and zero3_params)
        bspecs = MX.batch_specs(cfg, shape, env)
        batch_struct = input_specs(cfg, shape)
        dp = env.dp if shape.global_batch % env.dp_size == 0 else None
        if cfg.family == "encdec":
            fn = lambda p, b: M.forward(p, b, cfg, policy, mode="serve",
                                        impl="jnp", remat=False)
            out_sh = ((MX.tree_shardings(P(dp, None, None), env), None))
            jitted = jax.jit(
                fn,
                in_shardings=(MX.tree_shardings(pspecs, env),
                              MX.tree_shardings(bspecs, env)),
            )
            lowered = jitted.lower(params_struct, batch_struct)
        else:
            caches_struct = jax.eval_shape(
                lambda: M.init_cache(cfg, policy, shape.global_batch, shape.seq_len))
            cspecs = MX.cache_specs(caches_struct, cfg, shape, env)
            fn = lambda p, b, c: M.prefill_step(p, b, c, cfg, policy, impl="jnp")
            jitted = jax.jit(
                fn,
                in_shardings=(MX.tree_shardings(pspecs, env),
                              MX.tree_shardings(bspecs, env),
                              MX.tree_shardings(cspecs, env)),
                out_shardings=(env.named(P(dp, None, None)),
                               MX.tree_shardings(cspecs, env)),
                donate_argnums=(2,),
            )
            lowered = jitted.lower(params_struct, batch_struct, caches_struct)

    else:  # decode
        params_struct = jax.eval_shape(
            lambda: M.init_params(key, cfg, policy, mode="serve"))
        pspecs = MX.param_specs(params_struct, env,
                                fsdp=env.fsdp and zero3_params)
        enc_len = shape.seq_len // 2 if cfg.family == "encdec" else 0
        caches_struct = jax.eval_shape(
            lambda: M.init_cache(cfg, policy, shape.global_batch,
                                 shape.seq_len, enc_len=enc_len))
        cspecs = MX.cache_specs(caches_struct, cfg, shape, env)
        dp = env.dp if shape.global_batch % env.dp_size == 0 else None
        tok_struct = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
        pos_struct = jax.ShapeDtypeStruct((), jnp.int32)
        fn = lambda p, t, pos, c: M.decode_step(p, t, pos, c, cfg, policy,
                                                impl="jnp")
        jitted = jax.jit(
            fn,
            in_shardings=(MX.tree_shardings(pspecs, env),
                          env.named(P(dp, None)), env.named(P()),
                          MX.tree_shardings(cspecs, env)),
            out_shardings=(env.named(P(dp, None, None)),
                           MX.tree_shardings(cspecs, env)),
            donate_argnums=(3,),
        )
        lowered = jitted.lower(params_struct, tok_struct, pos_struct, caches_struct)

    return lowered


def _compile_costs(cfg, shape, env, policy, **kw) -> dict:
    """Lower + compile, return {'cost', 'collectives', 'n_layers', timings}."""
    t0 = time.time()
    lowered = lower_cell(cfg, shape, env, policy, **kw)
    t1 = time.time()
    compiled = lowered.compile()
    hlo = compiled.as_text()
    return {
        "n_layers": cfg.n_layers,
        "lower_s": round(t1 - t0, 2),
        "compile_s": round(time.time() - t1, 2),
        "cost": _scalar_costs(compiled.cost_analysis()),
        "collectives": hlo_collective_bytes(hlo),
        "memory": _mem_fields(compiled.memory_analysis()),
        "hlo_bytes": len(hlo),
    }


def _linfit(la: int, ca: float, lb: int, cb: float, l_true: int) -> float:
    per = (cb - ca) / max(lb - la, 1)
    if per < 0:
        # non-monotone fit (different fusion choices between variants):
        # fall back to proportional scaling from the larger point — never
        # extrapolate a negative cost.
        return cb * l_true / max(lb, 1)
    return ca + per * (l_true - la)


def build_cell(arch_id: str, shape_id: str, *, multi_pod: bool,
               policy_name: str, fsdp: bool = True, microbatches: int = 1,
               remat: bool = True, remat_policy: str = "full",
               causal_skip: bool = False, zero3_params: bool = True,
               ep2d: bool = False, skip_variants: bool = False):
    """Compile one cell (full rolled + two unrolled depth variants).
    Returns the artifact record."""
    cfg = configs.get_arch(arch_id)
    shape = SHAPES[shape_id]
    policy = get_policy(policy_name)
    mesh = MX.make_production_mesh(multi_pod=multi_pod)
    env = MX.AxisEnv(mesh=mesh, fsdp=fsdp, ep2d=ep2d)
    rec: dict = {
        "arch": arch_id, "shape": shape_id,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": int(np.prod(list(mesh.shape.values()))),
        "policy": policy_name, "kind": shape.kind, "fsdp": fsdp,
        "seq_len": shape.seq_len, "global_batch": shape.global_batch,
    }
    ok, reason = shape_applicable(cfg, shape)
    if not ok:
        rec.update(status="skip", reason=reason)
        return rec

    rec["remat_policy"] = remat_policy
    rec["microbatches"] = microbatches
    rec["causal_skip"] = causal_skip
    rec["zero3_params"] = zero3_params
    RF.FLAGS["causal_skip"] = causal_skip

    # 1. full config, scans rolled: the compile proof + realistic memory
    RF.FLAGS["unroll_scans"] = False
    full = _compile_costs(cfg, shape, env, policy, microbatches=microbatches,
                          remat=remat, remat_policy=remat_policy,
                          zero3_params=zero3_params)
    rec.update(lower_s=full["lower_s"], compile_s=full["compile_s"],
               memory=full["memory"], hlo_bytes=full["hlo_bytes"],
               cost_rolled=full["cost"], collectives_rolled=full["collectives"])

    # 2+3. reduced-depth unrolled variants -> exact per-layer accounting.
    # Single-pod only: the roofline table reads single-pod cells; the
    # multi-pod pass is the sharding proof (rolled compile) alone.
    if multi_pod:
        skip_variants = True
    if not skip_variants:
        RF.FLAGS["unroll_scans"] = True
        la, lb = variant_layers(cfg)
        va = _compile_costs(with_layers(cfg, la), shape, env, policy,
                            microbatches=microbatches, remat=remat,
                            remat_policy=remat_policy,
                            zero3_params=zero3_params)
        vb = _compile_costs(with_layers(cfg, lb), shape, env, policy,
                            microbatches=microbatches, remat=remat,
                            remat_policy=remat_policy,
                            zero3_params=zero3_params)
        RF.FLAGS["unroll_scans"] = False
        rec["variant_layers"] = [la, lb]
        rec["variant_compile_s"] = [va["compile_s"], vb["compile_s"]]
        cost = {}
        for k in set(va["cost"]) & set(vb["cost"]):
            if k.startswith(("flops", "bytes accessed", "transcendentals",
                             "optimal_seconds")):
                cost[k] = _linfit(la, va["cost"][k], lb, vb["cost"][k],
                                  cfg.n_layers)
        rec["cost"] = cost
        colls: dict = {}
        ops_all = set(va["collectives"]) | set(vb["collectives"])
        for op in ops_all:
            ba = va["collectives"].get(op, {"bytes": 0.0, "count": 0})
            bb = vb["collectives"].get(op, {"bytes": 0.0, "count": 0})
            colls[op] = {
                "bytes": max(0.0, _linfit(la, ba["bytes"], lb, bb["bytes"],
                                          cfg.n_layers)),
                "count": int(max(0, _linfit(la, ba["count"], lb, bb["count"],
                                            cfg.n_layers))),
            }
        rec["collectives"] = colls

    # usefulness ratio material (always from train-mode param structure)
    train_struct = jax.eval_shape(
        lambda: M.init_params(jax.random.key(0), cfg, get_policy("bf16"),
                              mode="train"))
    total, active = param_counts(train_struct, cfg)
    rec["params_total"] = total
    rec["params_active"] = active
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    rec["tokens"] = tokens
    rec["model_flops"] = (6.0 if shape.kind == "train" else 2.0) * active * tokens
    rec["status"] = "ok"
    return rec


def cell_path(out_dir: str, arch: str, shape: str, multi_pod: bool,
              tag: str = "") -> str:
    mesh = "2x16x16" if multi_pod else "16x16"
    sfx = f"__{tag}" if tag else ""
    return os.path.join(out_dir, f"{arch}__{shape}__{mesh}{sfx}.json")


def run_one(args) -> int:
    rec_path = cell_path(args.out, args.arch, args.shape, args.multi_pod,
                         args.tag)
    os.makedirs(args.out, exist_ok=True)
    if args.moe_dispatch_bits:
        RF.FLAGS["moe_dispatch_bits"] = args.moe_dispatch_bits
    try:
        rec = build_cell(args.arch, args.shape, multi_pod=args.multi_pod,
                         policy_name=args.policy, fsdp=not args.no_fsdp,
                         microbatches=args.microbatches, remat=not args.no_remat,
                         remat_policy=args.remat_policy,
                         causal_skip=args.causal_skip,
                         zero3_params=not args.zero2, ep2d=args.ep2d)
        rec["tag"] = args.tag
    except Exception as e:  # recorded, not raised: a failing cell is a bug report
        rec = {
            "arch": args.arch, "shape": args.shape,
            "mesh": "2x16x16" if args.multi_pod else "16x16",
            "status": "error", "error": f"{type(e).__name__}: {e}",
            "traceback": traceback.format_exc()[-4000:],
        }
    with open(rec_path, "w") as f:
        json.dump(rec, f, indent=1)
    status = rec.get("status")
    print(f"[dryrun] {args.arch} x {args.shape} x "
          f"{'2x16x16' if args.multi_pod else '16x16'}: {status} "
          f"(lower {rec.get('lower_s', '-')}s compile {rec.get('compile_s', '-')}s)")
    return 0 if status in ("ok", "skip") else 1


def run_all(args) -> int:
    import subprocess
    failures = 0
    for arch in sorted(configs.ARCHS):
        for shape in SHAPES:
            for mp in (False, True):
                path = cell_path(args.out, arch, shape, mp)
                if os.path.exists(path) and not args.force:
                    with open(path) as f:
                        if json.load(f).get("status") in ("ok", "skip"):
                            continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape, "--policy", args.policy,
                       "--out", args.out]
                if mp:
                    cmd.append("--multi-pod")
                try:
                    r = subprocess.run(cmd, env={**os.environ},
                                       timeout=args.cell_timeout)
                    failures += r.returncode != 0
                except subprocess.TimeoutExpired:
                    with open(path, "w") as f:
                        json.dump({"arch": arch, "shape": shape,
                                   "mesh": "2x16x16" if mp else "16x16",
                                   "status": "error",
                                   "error": f"timeout>{args.cell_timeout}s"}, f)
                    failures += 1
    print(f"[dryrun --all] done, {failures} failing cells")
    return 1 if failures else 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(configs.ARCHS))
    ap.add_argument("--shape", choices=sorted(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--policy", default="w4a8")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--cell-timeout", type=int, default=1200)
    ap.add_argument("--tag", default="", help="artifact suffix (hillclimb runs)")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--remat-policy", choices=["full", "dots"], default="full")
    ap.add_argument("--causal-skip", action="store_true")
    ap.add_argument("--zero2", action="store_true",
                    help="ZeRO-2: params TP-only, opt moments dp-sharded")
    ap.add_argument("--ep2d", action="store_true",
                    help="2D expert sharding: E over (model x data)")
    ap.add_argument("--moe-dispatch-bits", type=int, default=0,
                    help="int8 MoE dispatch payloads (serve): 8 or 0=off")
    ap.add_argument("--out", default=os.path.normpath(OUT_DIR))
    args = ap.parse_args()
    if args.all:
        return run_all(args)
    assert args.arch and args.shape, "--arch/--shape required (or --all)"
    return run_one(args)


if __name__ == "__main__":
    sys.exit(main())
