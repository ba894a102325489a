"""The program's side of a dense GQA transformer: its model config, the
policy class of each weight leaf, and its serving parameters built from the
family's codes. The only module of the family that imports the program
(``repro``); ``bench/program.py`` checks what it builds.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from bench import program as PG  # puts the program's src/ on the path
from bench import weights as Wt
from bench.families.dense_gqa import weights as FW
from repro import configs
from repro.core import pack as P

#: the program's layer class of each linear (the policy addresses classes)
LEAF_CLASS = {"wq": "attn_qkv", "wk": "attn_qkv", "wv": "attn_qkv",
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


def params(c: dict, key: jax.Array) -> dict:
    """The whole serving parameter tree from the seed's ``key``: one scan
    group of ``num_hidden_layers`` identical layers."""
    wb = c["precision"]["weight_bits"]
    L = c["num_hidden_layers"]
    shapes = FW.linear_shapes(c)

    def one(layer):  # one layer at a time keeps the random bits small
        codes = FW.layer_codes(key, c, layer, wb)
        return {n: P.pack(codes[n], wb[n]) for n in shapes}

    packed = jax.lax.map(one, jnp.arange(L))
    lin = {n: PG.linear(packed[n], jnp.full((L,), Wt.eps_w(shapes[n][1], wb[n])))
           for n in shapes}
    ones = jnp.ones((L, c["hidden_size"]), jnp.float32)
    block = {"norm1": {"scale": ones}, "norm2": {"scale": ones},
             "attn": {n: lin[n] for n in ("wq", "wk", "wv", "wo")},
             "mlp": {n: lin[n] for n in ("gate", "up", "down")}}
    head = FW.head_codes(key, c, wb["head"])
    return {"embed": {"table": FW.embed_table(key, c)},
            "final_norm": {"scale": jnp.ones((c["hidden_size"],), jnp.float32)},
            "head": PG.linear(P.pack(head, wb["head"]),
                              Wt.eps_w(c["hidden_size"], wb["head"])),
            "blocks": [block]}
