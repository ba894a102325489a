"""Optimizer substrate: AdamW (fp32 master state), LR schedules, gradient
clipping, and int8 gradient compression with error feedback — the paper's
quantization trick applied to the slowest collective (cross-pod all-reduce).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class OptCfg:
    lr: float = 3e-4
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10_000


def lr_at(cfg: OptCfg, step: jax.Array) -> jax.Array:
    step = step.astype(jnp.float32)
    warm = jnp.minimum(step / jnp.maximum(cfg.warmup_steps, 1), 1.0)
    prog = jnp.clip((step - cfg.warmup_steps)
                    / jnp.maximum(cfg.total_steps - cfg.warmup_steps, 1), 0.0, 1.0)
    cos = 0.5 * (1.0 + jnp.cos(jnp.pi * prog))
    return cfg.lr * warm * (0.1 + 0.9 * cos)


def adamw_init(params) -> dict:
    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    return {"m": zeros, "v": jax.tree.map(jnp.copy, zeros),
            "step": jnp.zeros((), jnp.int32)}


def global_norm(tree) -> jax.Array:
    sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
             for g in jax.tree.leaves(tree))
    return jnp.sqrt(sq)


def adamw_update(grads, state, params, cfg: OptCfg):
    """Returns (new_params, new_state, metrics)."""
    step = state["step"] + 1
    gn = global_norm(grads)
    clip = jnp.minimum(1.0, cfg.grad_clip / jnp.maximum(gn, 1e-9))
    lr = lr_at(cfg, step)
    b1, b2 = cfg.beta1, cfg.beta2
    c1 = 1.0 - b1 ** step.astype(jnp.float32)
    c2 = 1.0 - b2 ** step.astype(jnp.float32)

    def upd(p, g, m, v):
        g = g.astype(jnp.float32) * clip
        m_new = b1 * m + (1 - b1) * g
        v_new = b2 * v + (1 - b2) * g * g
        mhat = m_new / c1
        vhat = v_new / c2
        delta = mhat / (jnp.sqrt(vhat) + cfg.eps)
        if p.ndim >= 2:  # decoupled weight decay on matrices only
            delta = delta + cfg.weight_decay * p.astype(jnp.float32)
        p_new = (p.astype(jnp.float32) - lr * delta).astype(p.dtype)
        return p_new, m_new, v_new

    flat_p, treedef = jax.tree.flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_m = treedef.flatten_up_to(state["m"])
    flat_v = treedef.flatten_up_to(state["v"])
    out = [upd(p, g, m, v) for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
    new_params = treedef.unflatten([o[0] for o in out])
    new_state = {
        "m": treedef.unflatten([o[1] for o in out]),
        "v": treedef.unflatten([o[2] for o in out]),
        "step": step,
    }
    return new_params, new_state, {"grad_norm": gn, "lr": lr}


# ------------------------- int8 gradient compression with error feedback


def ef_state_init(params) -> dict:
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)


def _compress_allreduce_vec(v: jax.Array, axis_name: str) -> jax.Array:
    """Mean-all-reduce a flat fp32 vector with int8 on the wire.

    reduce-scatter phase: all_to_all of int8 shards + per-source scales,
    local fp32 accumulation; all-gather phase: requantized int8 shards.
    Wire bytes: 2 x N x 1B vs 2 x N x 4B for a ring fp32 all-reduce (4x cut).
    Must run inside shard_map with ``axis_name`` bound.
    """
    n_dev = jax.lax.axis_size(axis_name)
    n = v.shape[0]
    pad = -n % n_dev
    vp = jnp.pad(v, (0, pad))
    chunk = vp.shape[0] // n_dev

    scale = jnp.maximum(jnp.max(jnp.abs(vp)), 1e-30) / 127.0
    q = jnp.clip(jnp.round(vp / scale), -127, 127).astype(jnp.int8)

    # scatter: device d receives chunk d from every source (int8 wire)
    q_parts = jax.lax.all_to_all(q.reshape(n_dev, chunk), axis_name, 0, 0,
                                 tiled=False)  # (n_dev, chunk)
    scales = jax.lax.all_gather(scale, axis_name)  # (n_dev,)
    acc = jnp.sum(q_parts.astype(jnp.float32) * scales[:, None], axis=0) / n_dev

    # gather: requantize my reduced chunk, share int8 + scale
    s2 = jnp.maximum(jnp.max(jnp.abs(acc)), 1e-30) / 127.0
    q2 = jnp.clip(jnp.round(acc / s2), -127, 127).astype(jnp.int8)
    q2_all = jax.lax.all_gather(q2, axis_name)  # (n_dev, chunk) int8 wire
    s2_all = jax.lax.all_gather(s2, axis_name)  # (n_dev,)
    out = (q2_all.astype(jnp.float32) * s2_all[:, None]).reshape(-1)
    return out[:n]


def compressed_grad_allreduce(grads, err, axis_name: str):
    """Error-feedback int8 all-reduce over a pytree of local gradients.

    Returns (mean_grads, new_err). err accumulates the local quantization
    residual so compression bias vanishes over steps (EF-SGD).
    """
    flat_g, treedef = jax.tree.flatten(grads)
    flat_e = treedef.flatten_up_to(err)
    outs, new_errs = [], []
    for g, e in zip(flat_g, flat_e):
        target = g.astype(jnp.float32) + e
        vec = target.reshape(-1)
        reduced = _compress_allreduce_vec(vec, axis_name).reshape(g.shape)
        # residual of what *this device* contributed vs what it sent
        scale = jnp.maximum(jnp.max(jnp.abs(vec)), 1e-30) / 127.0
        sent = jnp.clip(jnp.round(vec / scale), -127, 127) * scale
        new_errs.append((vec - sent).reshape(g.shape))
        outs.append(reduced.astype(g.dtype))
    return treedef.unflatten(outs), treedef.unflatten(new_errs)
