"""Model families of the harness, found by the name a configuration gives.

A configuration file names its family under ``"harness"``; ``get(c)``
returns ``bench/families/<harness>/``. There is no default: a configuration
without the key, or with a family that is not here, fails with the list of
families that are. Everything the harness knows of a model's shape lives in
its family; the rest of ``bench/`` (the players, the check, the metrics,
``bench/program.py``) is the same for every family. So a configuration of a
new family is a configuration file, a directory here, a traffic mix, a
limits file, metric files, and entries in ``BENCHMARK.json``: no edit to a
file that is already here.

A family is four modules, each offering the names in ``CONTRACT``:

  * ``weights.py``: its leaves and their seeded integer codes, made with the
    generic ``bench/weights.py`` (``codes``, ``eps_w``, ``leaf_key``,
    ``seed_key``, ``vocab_padded``), so the program and the reference start
    from one set of numbers;
  * ``reference.py``: the plain reference, ``scores(seed, model_view(c),
    precision, seq, targets, kv_bits=, act_bits=)`` giving, at every
    position, the largest logit, the logit of the target and the argmax;
    ``model_view(c)`` is the part of the configuration it reads;
  * ``counts.py``: operations and bytes from the configuration's sizes: a
    token through the layers (``linear_ops``, ``token_flops``), the LM head
    (``head_ops``), attention over a range of contexts (``attn_ops_range``),
    and the KV bytes one decode lane reads (``paged_attn_bytes``);
  * ``program.py``: the program's side, and the only module of a family
    that imports the program (``repro``): ``arch(c)``, the program's model
    config; ``LEAF_CLASS``, the policy class of each weight leaf the
    configuration's ``precision.weight_bits`` names; ``params(c, key)``, the
    whole serving parameter tree built on the device from the codes.

``weights.py``, ``reference.py`` and ``counts.py`` import nothing of the
program, nor ``bench.program``: the yardstick does not move with it.

What the next family needs, and the generic code does not assume away:

  * several scan groups: ``params`` returns the whole tree, its ``blocks``
    a list with one stacked entry per group (dense layers, then experts),
    and ``bench/program.py`` checks only that it equals what the program's
    own ``init_params(mode="serve")`` lays out;
  * unquantized leaves (a router): ``weight_bits`` states ``"bf16"`` for
    such a leaf, and the precision check requires its ``LEAF_CLASS`` to be
    unquantized in the program's policy;
  * stacked expert leaves, ``(E, d_out, d_in)`` codes: their shape is the
    family's alone, as is packing them;
  * a KV record that is not K and V per head (latent codes): its bytes come
    from the family's ``counts.py`` only.
"""

from __future__ import annotations

import functools
import importlib
import os

HERE = os.path.dirname(os.path.abspath(__file__))

#: the names each module of a family offers the generic code
CONTRACT = {
    "weights": ("LEAVES", "layer_codes", "embed_table", "head_codes"),
    "reference": ("MODEL_KEYS", "model_view", "scores"),
    "counts": ("linear_ops", "head_ops", "attn_ops_range", "token_flops",
               "paged_attn_bytes"),
    "program": ("LEAF_CLASS", "arch", "params"),
}


class Family:
    """One family's modules; ``program`` is imported when first asked for,
    so a caller that needs only the yardstick never loads the program."""

    def __init__(self, name: str):
        self.name = name
        self.weights = self._module("weights")
        self.reference = self._module("reference")
        self.counts = self._module("counts")

    def _module(self, part: str):
        return importlib.import_module(f"{__name__}.{self.name}.{part}")

    @property
    def program(self):
        return self._module("program")


def names() -> list[str]:
    """The families here: directories that hold the four modules."""
    return sorted(d for d in os.listdir(HERE)
                  if all(os.path.isfile(os.path.join(HERE, d, m + ".py")) for m in CONTRACT))


@functools.cache
def _load(name: str) -> Family:
    return Family(name)


def get(c: dict) -> Family:
    """The family configuration ``c`` names under ``"harness"``."""
    name = c.get("harness")
    if name not in names():
        raise LookupError(f"configuration {c.get('name')!r} names harness family {name!r}; "
                          f"the families are {names()}")
    return _load(name)
