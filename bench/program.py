"""The system under test, as the harness drives it: the program's model
config, precision policy, serving parameters and engine, built from a
configuration file of ``bench/configs`` and the seed.

This is the one module of the benchmark that imports the program
(``repro``). It hands the program the codes of ``bench/weights.py``, packed
in the program's own serving layout, and checks that the program runs the
precision the configuration states before anything is timed.
"""

from __future__ import annotations

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp

from bench import weights as Wt

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if os.path.join(ROOT, "src") not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, "src"))

from repro import configs  # noqa: E402
from repro.core import pack as P  # noqa: E402
from repro.core.policy import get_policy  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.serve import SamplingParams, ServeEngine  # noqa: E402
from repro.serve.trace import Tracer  # noqa: E402

#: the program's layer class of each linear (the policy addresses classes)
_CLASS = {"wq": "attn_qkv", "wk": "attn_qkv", "wv": "attn_qkv",
          "wo": "attn_out", "gate": "ffn_in", "up": "ffn_in",
          "down": "ffn_out", "head": "head"}


def arch(c: dict):
    """The program's ArchConfig with every size taken from the file."""
    base = configs.get_arch(c["arch"])
    return dataclasses.replace(
        base, n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], kv_heads=c["num_key_value_heads"],
        head_dim=c["head_dim"], d_ff=c["intermediate_size"],
        vocab=c["vocab_size"], window=c.get("sliding_window"),
        rope_theta=float(c["rope_theta"]))


def policy(c: dict):
    """The program's policy, checked against the stated precision: a run in
    which the program computes another precision than the file states is no
    run of this configuration."""
    pol = get_policy(c["policy"])
    prec = c["precision"]
    bad = []
    for leaf, cls in _CLASS.items():
        lp = pol.of(cls)
        if (lp.w_bits, lp.x_bits) != (prec["weight_bits"][leaf], prec["act_bits"]):
            bad.append(f"{leaf}: program w{lp.w_bits}a{lp.x_bits}")
    if pol.kv_cache_bits != prec["kv_bits"]:
        bad.append(f"kv: program {pol.kv_cache_bits}")
    if bad:
        raise SystemExit(f"policy {pol.name} departs from the configuration: {bad}")
    return pol


def _linear(packed, eps) -> dict:
    return {"w_packed": packed, "eps_w": eps,
            "beta": jnp.full(eps.shape, Wt.ACT_CLIP, jnp.float32)}


def make_params(c: dict, seed: int, acfg, pol):
    """The program's serving parameters, made on the device in one jitted
    call from the seed; their tree and shapes must equal what the program's
    own ``init_params(mode="serve")`` lays out."""
    wb = c["precision"]["weight_bits"]
    L = c["num_hidden_layers"]
    shapes = Wt.linear_shapes(c)

    def build(key):
        def one(layer):  # one layer at a time keeps the random bits small
            codes = Wt.layer_codes(key, c, layer, wb)
            return {n: P.pack(codes[n], wb[n]) for n in shapes}

        packed = jax.lax.map(one, jnp.arange(L))
        lin = {n: _linear(packed[n], jnp.full((L,), Wt.eps_w(shapes[n][1], wb[n])))
               for n in shapes}
        ones = jnp.ones((L, c["hidden_size"]), jnp.float32)
        block = {"norm1": {"scale": ones}, "norm2": {"scale": ones},
                 "attn": {n: lin[n] for n in ("wq", "wk", "wv", "wo")},
                 "mlp": {n: lin[n] for n in ("gate", "up", "down")}}
        head = Wt.head_codes(key, c, wb["head"])
        return {"embed": {"table": Wt.embed_table(key, c)},
                "final_norm": {"scale": jnp.ones((c["hidden_size"],), jnp.float32)},
                "head": _linear(P.pack(head, wb["head"]),
                                Wt.eps_w(c["hidden_size"], wb["head"])),
                "blocks": [block]}

    want = jax.eval_shape(lambda k: M.init_params(k, acfg, pol, mode="serve"),
                          jax.random.key(0))
    got = jax.eval_shape(build, Wt.seed_key(seed))
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise SystemExit("the program's serving parameter layout changed: "
                         f"{jax.tree.map(lambda a: a.shape, want)}")
    return jax.jit(build)(Wt.seed_key(seed))


def engine(params, acfg, pol, eng: dict, *, traced: bool):
    """``ServeEngine`` with the cell's settings; ``traced`` attaches the
    program's own span recorder. The page pool holds every slot at
    ``s_max``."""
    ps = eng["page_size"]
    n_pages = -(-eng["n_slots"] * eng["s_max"] // ps) + 1  # + the scratch page
    return ServeEngine(
        params, acfg, pol, n_slots=eng["n_slots"], s_max=eng["s_max"],
        cache="paged", page_size=ps, n_pages=n_pages, mixed=True,
        mixed_budget=eng["mixed_budget"], prefill_chunk=eng["mixed_budget"],
        inflight=eng["inflight"], fused_attn=True,
        trace=Tracer(capacity=1 << 20) if traced else None)


def greedy(max_new: int):
    return SamplingParams(max_new=int(max_new))
