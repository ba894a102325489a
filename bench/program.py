"""The system under test, as the harness drives it: the program's precision
policy, serving parameters and engine, built from a configuration file of
``bench/configs`` and the seed, for any model family.

Besides each family's ``program.py`` (``bench/families``), this is the one
module of the benchmark that imports the program (``repro``). The family
builds the program's parameters from the benchmark's own codes; this module
checks, before anything is timed, that the program runs the precision the
configuration states and lays its parameters out as the family built them.
"""

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp

from bench import families
from bench import weights as Wt

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if os.path.join(ROOT, "src") not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, "src"))

from repro.core.policy import get_policy  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.serve import SamplingParams, ServeEngine  # noqa: E402
from repro.serve.trace import Tracer  # noqa: E402


def policy(c: dict):
    """The program's policy, checked against the stated precision: a run in
    which the program computes another precision than the file states is no
    run of this configuration. A leaf stated ``"bf16"`` must be unquantized."""
    pol = get_policy(c["policy"])
    prec = c["precision"]
    bad = []
    for leaf, cls in families.get(c).program.LEAF_CLASS.items():
        lp, want = pol.of(cls), prec["weight_bits"][leaf]
        if (lp.w_bits, lp.x_bits) != ((None, None) if want == "bf16" else (want, prec["act_bits"])):
            bad.append(f"{leaf}: program w{lp.w_bits}a{lp.x_bits}")
    if pol.kv_cache_bits != prec["kv_bits"]:
        bad.append(f"kv: program {pol.kv_cache_bits}")
    if bad:
        raise SystemExit(f"policy {pol.name} departs from the configuration: {bad}")
    return pol


def linear(packed, eps) -> dict:
    """One quantized linear in the program's serving layout."""
    return {"w_packed": packed, "eps_w": eps,
            "beta": jnp.full(eps.shape, Wt.ACT_CLIP, jnp.float32)}


def make_params(c: dict, seed: int, acfg, pol):
    """The program's serving parameters, made on the device in one jitted
    call from the seed by the configuration's family; their tree and shapes
    must equal what the program's own ``init_params(mode="serve")`` lays
    out."""
    fam = families.get(c).program

    def build(key):
        return fam.params(c, key)

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
