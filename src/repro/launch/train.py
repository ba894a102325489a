"""Production training driver: pjit train loop on an arbitrary mesh with
checkpoint/resume, preemption trap, straggler monitor, int8-compressed
gradient all-reduce (shard_map), and deterministic host-sharded data.

On real hardware:   python -m repro.launch.train --arch zamba2-1.2b \
                        --shape train_4k --mesh-data 16 --mesh-model 16
On this container:  PYTHONPATH=src python -m repro.launch.train \
                        --smoke --steps 20     (reduced arch, 1x1 mesh)
"""

from __future__ import annotations

import argparse
import time
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro import compile_cache, configs
from repro.checkpoint import store
from repro.configs.shapes import SHAPES, ShapeCfg
from repro.core.policy import get_policy
from repro.data.pipeline import Pipeline
from repro.launch import mesh as MX
from repro.serve.engine import StepMonitor
from repro.train import optimizer as opt
from repro.train import step as T


def make_mesh(data: int, model: int, pod: int = 1) -> Mesh:
    n = data * model * pod
    devs = np.asarray(jax.devices()[:n])
    if pod > 1:
        return Mesh(devs.reshape(pod, data, model), ("pod", "data", "model"))
    return Mesh(devs.reshape(data, model), ("data", "model"))


class TrainSetup(NamedTuple):
    init: Callable[[], Any]  # () -> train state, created sharded
    step: Callable[[Any, Any], Any]  # (state, batch) -> (state, metrics)
    state_shape: Any  # ShapeDtypeStruct tree of the state
    state_shardings: Any
    batch_shardings: Any


def setup(cfg, policy, tcfg: T.TrainCfg, shape: ShapeCfg,
          mesh: Mesh) -> TrainSetup:
    """The sharded train state and step for ``mesh`` (FSDP parameters and
    moments, data-parallel batch). ``init`` creates the state already
    sharded (``init_train_state`` jitted with ``out_shardings``), so a state
    larger than one device — bf16 params plus f32 Adam moments at full
    width — never materializes on one device."""
    env = MX.AxisEnv(mesh=mesh, fsdp=True)

    def make():
        return T.init_train_state(jax.random.key(0), cfg, policy, tcfg)

    state_shape = jax.eval_shape(make)
    pspecs = MX.param_specs(state_shape["params"], env)
    sspecs = {"params": pspecs, "opt": {"m": pspecs, "v": pspecs, "step": P()}}
    if "ef" in state_shape:
        sspecs["ef"] = pspecs
    sshard = MX.tree_shardings(sspecs, env)
    bshard = MX.tree_shardings(MX.batch_specs(cfg, shape, env), env)
    step = jax.jit(
        T.make_train_step(cfg, policy, tcfg, impl="jnp"),
        in_shardings=(sshard, bshard),
        out_shardings=(sshard, None),
        donate_argnums=(0,),
    )
    return TrainSetup(jax.jit(make, out_shardings=sshard), step, state_shape,
                      sshard, bshard)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b", choices=sorted(configs.ARCHS))
    ap.add_argument("--shape", default="train_4k", choices=sorted(SHAPES))
    ap.add_argument("--policy", default="w4a8")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--mesh-pod", type=int, default=1)
    ap.add_argument("--mesh-data", type=int, default=1)
    ap.add_argument("--mesh-model", type=int, default=1)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--grad-compression", choices=["none", "int8_ef"], default="none")
    ap.add_argument("--ckpt", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config + tiny shape (CPU-runnable)")
    args = ap.parse_args()
    compile_cache.enable()

    cfg = configs.get_arch(args.arch)
    shape = SHAPES[args.shape]
    if args.smoke:
        cfg = configs.reduced(cfg)
        shape = ShapeCfg("smoke", 32, 4, "train")
    policy = get_policy(args.policy)
    tcfg = T.TrainCfg(
        opt=opt.OptCfg(total_steps=args.steps),
        microbatches=args.microbatches,
        grad_compression=None if args.grad_compression == "none" else args.grad_compression,
    )

    mesh = make_mesh(args.mesh_data, args.mesh_model, args.mesh_pod)
    print(f"mesh {dict(mesh.shape)} arch={cfg.name} policy={policy.name}")
    ts = setup(cfg, policy, tcfg, shape, mesh)

    start = 0
    ck = store.Checkpointer(args.ckpt, keep=3)
    if args.resume and store.latest_step(args.ckpt) is not None:
        state, start = store.load(args.ckpt, ts.state_shape,
                                  shardings=ts.state_shardings)
        print(f"resumed from step {start} (elastic reshard onto current mesh)")
    else:
        state = ts.init()
    latest = {"step": start, "state": state}
    ck.install_preemption_handler(lambda: (latest["step"], latest["state"]))

    pipe = Pipeline(cfg, shape, start_step=start)
    mon = StepMonitor()
    for _ in range(start, args.steps):
        step_i, batch = next(pipe)
        t0 = time.perf_counter()
        state, metrics = ts.step(state, jax.tree.map(jnp.asarray, batch))
        jax.block_until_ready(metrics["loss"])
        slow = mon.observe(time.perf_counter() - t0)
        latest.update(step=step_i + 1, state=state)
        if (step_i + 1) % 10 == 0 or step_i == start:
            print(f"step {step_i + 1:5d} loss {float(metrics['loss']):.4f} "
                  f"lr {float(metrics['lr']):.2e}"
                  f"{'  [STRAGGLER]' if slow else ''}", flush=True)
        if (step_i + 1) % args.ckpt_every == 0:
            ck.save_async(step_i + 1, state)
    ck.wait()
    pipe.close()
    print(f"trained to step {args.steps}; stragglers={mon.stragglers}")


if __name__ == "__main__":
    main()
