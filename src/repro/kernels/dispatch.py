"""Kernel dispatch registry — the paper's 27-kernel library as a first-class
table instead of ad-hoc parameterization.

PULP-NN ships one inner loop per (ifmap, weight, ofmap) precision permutation;
the library's value is that *every* cell of that matrix exists, is correct,
and is fast. This module makes the matrix explicit: every kernel variant is a
``KernelEntry`` registered under a ``KernelKey`` ``(op, x_bits, w_bits,
y_bits, impl)``, coverage of all 27 permutations is validated at import time
(a missing cell is an ImportError, not a latent runtime KeyError), and every
call in ops.py routes through :func:`lookup` — which also counts dispatches,
so serving/benchmark layers can report which cells a workload actually hits.

Ops in the registry:
  * ``mpmm``    — keyed on the full (x_bits, w_bits, y_bits) permutation,
  * ``conv2d``  — same 27-cell space (the paper's conv library),
  * ``qntpack`` — keyed on y_bits only (x/w are None),
  * ``wdqmm``   — keyed on w_bits only (weight-only dequant matmul).

Each op registers both backends:
  * ``pallas`` — the Pallas TPU kernel (interpret=True off-TPU),
  * ``jnp``    — bit-exact plain-XLA twin (CPU training/tests/dry-run).

Tile-size selection is *not* here: entries declare which tile parameters they
accept (``tunable``); resolution of actual (bm, bn, bk) values is
kernels/tuning.py's job.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
from typing import Callable, Optional

import jax

from repro.core.policy import BITS, PERMUTATIONS, perm_name


@dataclasses.dataclass(frozen=True)
class KernelKey:
    """Identity of one cell of the kernel matrix."""

    op: str
    x_bits: Optional[int]
    w_bits: Optional[int]
    y_bits: Optional[int]
    impl: str  # "pallas" | "jnp"

    def __str__(self) -> str:
        bits = "_".join(
            "x" if b is None else str(b) for b in (self.x_bits, self.w_bits, self.y_bits)
        )
        return f"{self.op}[{bits}]@{self.impl}"


@dataclasses.dataclass(frozen=True)
class KernelEntry:
    """One registered kernel variant.

    ``fn`` is the raw kernel callable with the permutation already bound;
    ``tunable`` names the tile kwargs the callable accepts (subject to
    autotuning); ``name`` is the PULP-NN-style kernel name used in caches,
    benchmark rows, and error messages.
    """

    key: KernelKey
    fn: Callable
    name: str
    tunable: tuple[str, ...] = ()


_REGISTRY: dict[KernelKey, KernelEntry] = {}

#: How many times each kernel cell has been dispatched (process-wide).
#: ``serve.engine.ServeEngine.kernel_stats()`` snapshots this.
DISPATCH_COUNTS: collections.Counter = collections.Counter()

IMPLS = ("pallas", "jnp")

#: KV-cache storage widths the quantizer emits (models.attention.kv_quantize):
#: bf16 passthrough, int8, packed int4. The fused decode-attention ops key
#: their w_bits axis on this set.
KV_BITS = (None, 8, 4)


def register(
    op: str,
    *,
    x_bits: Optional[int] = None,
    w_bits: Optional[int] = None,
    y_bits: Optional[int] = None,
    impl: str,
    fn: Callable,
    name: Optional[str] = None,
    tunable: tuple[str, ...] = (),
) -> KernelEntry:
    if impl not in IMPLS:
        raise ValueError(f"impl must be one of {IMPLS}, got {impl!r}")
    key = KernelKey(op, x_bits, w_bits, y_bits, impl)
    if key in _REGISTRY:
        raise ValueError(f"duplicate kernel registration: {key}")
    entry = KernelEntry(key, fn, name or str(key), tunable)
    _REGISTRY[key] = entry
    return entry


def resolve_impl(impl: str) -> str:
    """``auto`` -> pallas on TPU, jnp elsewhere (same rule the model zoo and
    serving engine rely on, so one code path runs in every environment)."""
    if impl != "auto":
        return impl
    return "pallas" if jax.default_backend() == "tpu" else "jnp"


def lookup(
    op: str,
    *,
    x_bits: Optional[int] = None,
    w_bits: Optional[int] = None,
    y_bits: Optional[int] = None,
    impl: str = "auto",
) -> KernelEntry:
    """Route one call: returns the registered entry, counting the dispatch."""
    key = KernelKey(op, x_bits, w_bits, y_bits, resolve_impl(impl))
    entry = _REGISTRY.get(key)
    if entry is None:
        have = sorted(str(k) for k in _REGISTRY if k.op == op)
        raise KeyError(
            f"no kernel registered for {key} — the precision permutation is "
            f"outside the library. Registered {op} cells: {have}"
        )
    DISPATCH_COUNTS[key] += 1
    return entry


def registered_keys(op: Optional[str] = None) -> list[KernelKey]:
    return sorted(
        (k for k in _REGISTRY if op is None or k.op == op),
        key=lambda k: (k.op, k.impl, k.x_bits or 0, k.w_bits or 0, k.y_bits or 0),
    )


def coverage(op: str, impl: str) -> set[tuple]:
    """The set of (x_bits, w_bits, y_bits) cells registered for op@impl."""
    return {
        (k.x_bits, k.w_bits, k.y_bits)
        for k in _REGISTRY
        if k.op == op and k.impl == impl
    }


def dispatch_stats() -> dict[str, int]:
    """Snapshot of per-cell dispatch counts (stringified keys, sorted)."""
    return {str(k): v for k, v in sorted(DISPATCH_COUNTS.items(), key=lambda kv: str(kv[0]))}


def reset_dispatch_counts() -> None:
    DISPATCH_COUNTS.clear()


def validate_coverage() -> None:
    """The import-time gate: every cell of the paper's matrix must exist.

    mpmm and conv2d must cover all 27 (x, w, y) permutations on both backends;
    qntpack must cover every y_bits; wdqmm every w_bits. Raises RuntimeError
    listing the missing cells otherwise.
    """
    missing: list[str] = []
    full = set(PERMUTATIONS)
    for op in ("mpmm", "conv2d"):
        for impl in IMPLS:
            for cell in sorted(full - coverage(op, impl)):
                missing.append(f"{op}[{cell[0]}_{cell[1]}_{cell[2]}]@{impl}")
    for impl in IMPLS:
        have_y = {c[2] for c in coverage("qntpack", impl)}
        for b in BITS:
            if b not in have_y:
                missing.append(f"qntpack[y={b}]@{impl}")
        have_w = {c[1] for c in coverage("wdqmm", impl)}
        for b in BITS:
            if b not in have_w:
                missing.append(f"wdqmm[w={b}]@{impl}")
        # the paged KV movers are storage-dtype-agnostic: one cell per backend
        for op in ("paged_gather", "paged_scatter", "paged_copy"):
            if not coverage(op, impl):
                missing.append(f"{op}@{impl}")
        # fused decode attention is keyed on the KV storage width (w_bits):
        # bf16 (None) plus every packed width the cache quantizer emits
        for op in ("paged_attn", "paged_mla_attn"):
            have_kv = {c[1] for c in coverage(op, impl)}
            for b in KV_BITS:
                if b not in have_kv:
                    missing.append(f"{op}[kv={b}]@{impl}")
    if missing:
        raise RuntimeError(
            f"kernel matrix has {len(missing)} unregistered cells: {missing}"
        )


def cells_for_policy(policy) -> list[KernelKey]:
    """The kernel-matrix cells a PrecisionPolicy's serving path routes
    through (one per distinct quantized LayerPrecision): fully-quantized
    layers hit mpmm (signed-activation variant, f32 out — y_bits=8 requant
    vector per core/linear.py), weight-only layers hit wdqmm. Used by the
    serving engine to validate coverage up front and warm the right cells."""
    from repro.core.policy import LAYER_CLASSES

    cells: set[KernelKey] = set()
    for cls in LAYER_CLASSES:
        lp = policy.of(cls)
        if not lp.quantized:
            continue
        if lp.act_quantized:
            cells.add(KernelKey("mpmm", lp.x_bits, lp.w_bits, 8, "pallas"))
        else:
            cells.add(KernelKey("wdqmm", None, lp.w_bits, None, "pallas"))
    return sorted(cells, key=str)


def ensure_policy_supported(policy) -> None:
    """Fail fast (KeyError) if any cell a policy needs is unregistered —
    engine construction time, not the first decode step."""
    for cell in cells_for_policy(policy):
        for impl in IMPLS:
            key = dataclasses.replace(cell, impl=impl)
            if key not in _REGISTRY:
                raise KeyError(
                    f"policy {getattr(policy, 'name', policy)!r} needs "
                    f"unregistered kernel cell {key}")


# --------------------------------------------------------------------------
# Registration of the library. Permutations are bound eagerly (functools
# .partial) so each cell is a distinct callable with its own name — the
# registry IS the 27-kernel library, not a parameterized single kernel.
# --------------------------------------------------------------------------


def _register_library() -> None:
    from repro.kernels import ref
    from repro.kernels.conv2d import conv2d_pallas
    from repro.kernels.mpmm import mpmm_pallas
    from repro.kernels.qntpack import qntpack_pallas
    from repro.kernels.wdqmm import wdqmm_pallas, wdqmm_ref

    for x_bits, w_bits, y_bits in PERMUTATIONS:
        name = perm_name(x_bits, w_bits, y_bits)
        register(
            "mpmm", x_bits=x_bits, w_bits=w_bits, y_bits=y_bits, impl="pallas",
            fn=functools.partial(mpmm_pallas, x_bits=x_bits, w_bits=w_bits, y_bits=y_bits),
            name=name, tunable=("bm", "bn", "bk"),
        )
        register(
            "mpmm", x_bits=x_bits, w_bits=w_bits, y_bits=y_bits, impl="jnp",
            fn=functools.partial(ref.mpmm_ref, x_bits=x_bits, w_bits=w_bits, y_bits=y_bits),
            name=name + "_ref",
        )
        register(
            "conv2d", x_bits=x_bits, w_bits=w_bits, y_bits=y_bits, impl="pallas",
            fn=functools.partial(conv2d_pallas, x_bits=x_bits, w_bits=w_bits, y_bits=y_bits),
            name=f"conv3x3_u{x_bits}_i{w_bits}_u{y_bits}",
            tunable=("bh",),
        )
        register(
            "conv2d", x_bits=x_bits, w_bits=w_bits, y_bits=y_bits, impl="jnp",
            fn=functools.partial(ref.conv2d_ref, x_bits=x_bits, w_bits=w_bits, y_bits=y_bits),
            name=f"conv3x3_u{x_bits}_i{w_bits}_u{y_bits}_ref",
        )
    for y_bits in BITS:
        register(
            "qntpack", y_bits=y_bits, impl="pallas",
            fn=functools.partial(qntpack_pallas, y_bits=y_bits),
            name=f"qntpack_u{y_bits}", tunable=("bm",),
        )
        register(
            "qntpack", y_bits=y_bits, impl="jnp",
            fn=functools.partial(ref.qntpack_ref, y_bits=y_bits),
            name=f"qntpack_u{y_bits}_ref",
        )
    for w_bits in BITS:
        register(
            "wdqmm", w_bits=w_bits, impl="pallas",
            fn=functools.partial(wdqmm_pallas, w_bits=w_bits),
            name=f"wdqmm_i{w_bits}", tunable=("bm", "bn", "bk"),
        )
        register(
            "wdqmm", w_bits=w_bits, impl="jnp",
            fn=functools.partial(wdqmm_ref, w_bits=w_bits),
            name=f"wdqmm_i{w_bits}_ref",
        )
    # paged KV cache movers (serve/cache.py page pool <-> logical rows).
    # Storage-dtype-agnostic (int8 packed, f32 scales, bf16 latents alike),
    # so a single cell per backend; the tunable knob is the page size itself,
    # resolved through tuning op "kvpage" by the PagePool.
    from repro.kernels.paged_gather import (
        paged_copy_pallas,
        paged_copy_ref,
        paged_gather_pallas,
        paged_gather_ref,
        paged_scatter_pallas,
        paged_scatter_ref,
    )

    register("paged_gather", impl="pallas", fn=paged_gather_pallas,
             name="paged_gather")
    register("paged_gather", impl="jnp", fn=paged_gather_ref,
             name="paged_gather_ref")
    register("paged_scatter", impl="pallas", fn=paged_scatter_pallas,
             name="paged_scatter")
    register("paged_scatter", impl="jnp", fn=paged_scatter_ref,
             name="paged_scatter_ref")
    # the prefix cache's copy-on-write page clone (serve/prefix.py)
    register("paged_copy", impl="pallas", fn=paged_copy_pallas,
             name="paged_copy")
    register("paged_copy", impl="jnp", fn=paged_copy_ref,
             name="paged_copy_ref")
    # fused decode attention: block-table walk + in-kernel dequant, one cell
    # per KV storage width (bf16 / int8 / packed int4). The tunable knob is
    # the dense-view block size (tuning op "paged_attn"); paged callers
    # inherit the pool's page size instead.
    from repro.kernels.paged_attn import (
        paged_attn_pallas,
        paged_attn_ref,
        paged_mla_attn_pallas,
        paged_mla_attn_ref,
    )

    for kv_bits in KV_BITS:
        tag = "bf16" if kv_bits is None else f"kv{kv_bits}"
        register(
            "paged_attn", w_bits=kv_bits, impl="pallas",
            fn=functools.partial(paged_attn_pallas, bits=kv_bits),
            name=f"paged_attn_{tag}", tunable=("bs",),
        )
        register(
            "paged_attn", w_bits=kv_bits, impl="jnp",
            fn=functools.partial(paged_attn_ref, bits=kv_bits),
            name=f"paged_attn_{tag}_ref",
        )
        register(
            "paged_mla_attn", w_bits=kv_bits, impl="pallas",
            fn=functools.partial(paged_mla_attn_pallas, bits=kv_bits),
            name=f"paged_mla_attn_{tag}", tunable=("bs",),
        )
        register(
            "paged_mla_attn", w_bits=kv_bits, impl="jnp",
            fn=functools.partial(paged_mla_attn_ref, bits=kv_bits),
            name=f"paged_mla_attn_{tag}_ref",
        )


_register_library()
validate_coverage()
