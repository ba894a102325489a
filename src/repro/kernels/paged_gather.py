"""Paged KV gather/scatter — page-pool cache blocks to/from logical rows.

The paged cache stores quantized K/V in a global pool of fixed-size token
pages ``(n_pages, page_size, ...)``; a per-slot block table maps a request's
logical block index to its physical page. These kernels move packed pages
between the pool and the contiguous logical view the attention math consumes:

  * ``paged_gather``  — pool + block table -> ``(B, n_blocks * page_size,
    ...)`` logical rows (the decode read path: one DMA per page, indexed via
    a scalar-prefetched block table — the TPU analogue of vLLM's paged
    attention gather, moving data at *quantized* width so the paper's
    footprint win carries straight through to HBM traffic),
  * ``paged_scatter`` — write one new token row per sequence into the pool
    at ``block_table[b, pos // page_size], pos % page_size`` (the decode
    write path; the pool is aliased in/out so untouched pages persist),
  * ``paged_copy``    — duplicate whole pages inside the pool (``dst[i] =
    src[i]`` page-for-page, aliased in/out). This is the prefix cache's
    copy-on-write primitive: a request that diverges mid-page clones the
    shared page before writing, so the original stays bit-frozen for its
    other readers (serve/prefix.py).

All three ship the usual pair of backends: the Pallas kernel (interpreted
off-TPU) and a bit-exact jnp twin (plain XLA gather/scatter). Registered in
kernels/dispatch.py; the *page size* itself resolves through kernels/tuning
(op ``kvpage``) like any other tile parameter.

Layout note: every block is one whole page ``(1, page_size, ...)`` of the
leaf at its own rank (heads x packed features for GQA, 1 x kv_lora for MLA
latents, bare scales) — the page is the unit of transfer, and a block whose
trailing dims are the pool's own is one Mosaic accepts at any leaf rank.
Nothing is reshaped across the tiled trailing dims, which on a TPU would
copy the pool.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# ------------------------------------------------------------------ gather


def paged_gather_pallas(pool: jax.Array, block_table: jax.Array, *,
                        interpret: bool) -> jax.Array:
    """pool (P, ps, ...) gathered by block_table (B, NB) int32 ->
    (B, NB * ps, ...). One grid step copies one page; the block table is
    scalar-prefetched so the page index is known before the DMA issues."""
    ps, tail = pool.shape[1], pool.shape[2:]
    zeros = (0,) * len(tail)
    B, NB = block_table.shape

    def kernel(bt_ref, pool_ref, out_ref):
        del bt_ref  # consumed by the index_map
        out_ref[0, 0] = pool_ref[0]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, NB),
        in_specs=[pl.BlockSpec((1, ps, *tail),
                               lambda b, j, bt: (bt[b, j], 0) + zeros)],
        out_specs=pl.BlockSpec((1, 1, ps, *tail),
                               lambda b, j, bt: (b, j, 0) + zeros),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, NB, ps, *tail), pool.dtype),
        interpret=interpret,
        name="paged_gather",
    )(block_table, pool)
    return out.reshape(B, NB * ps, *tail)  # merges two untiled leading dims


def paged_gather_ref(pool: jax.Array, block_table: jax.Array) -> jax.Array:
    """jnp twin: plain XLA gather along the page axis."""
    B, NB = block_table.shape
    g = pool[block_table]  # (B, NB, ps, ...)
    return g.reshape(B, NB * pool.shape[1], *pool.shape[2:])


# ----------------------------------------------------------------- scatter


def paged_scatter_pallas(pool: jax.Array, new: jax.Array, pos: jax.Array,
                         block_table: jax.Array, *,
                         interpret: bool) -> jax.Array:
    """Write ``new`` (B, S_new, ...) into ``pool`` (P, ps, ...) at logical
    position ``pos`` (B,) per sequence, through the block table. The pool is
    aliased input->output, so pages outside the written rows persist; rows
    whose block-table entry is the reserved scratch page (0) land in trash.

    One grid step writes one token row into its page. The blocks are whole
    pages, since Mosaic refuses a one-row block whose trailing dims are not
    the pool's own. A page is read into the output block when the step's page
    differs from the previous step's; consecutive rows of one page then
    accumulate in VMEM and the page is written back once. A sequence's rows
    are consecutive grid steps and real pages are never shared by two
    writers, so only the scratch page is ever revisited — and its contents
    are trash by contract.
    """
    ps, tail = pool.shape[1], pool.shape[2:]
    B, S_new = new.shape[:2]
    nb = block_table.shape[1]
    zeros = (0,) * len(tail)

    def row_page(bt, pos, b, s):
        idx = pos[b] + s
        blk = idx // ps
        # rows past the block table trash-bin to the scratch page (0), the
        # same drop semantics as the jnp twin's mode="fill" gather — a bare
        # bt[b, blk] would CLAMP to the last real page and corrupt it
        page = jnp.where(blk < nb, bt[b, jnp.minimum(blk, nb - 1)], 0)
        return page, idx % ps

    def kernel(bt_ref, pos_ref, new_ref, pool_ref, out_ref):
        b, s = pl.program_id(0), pl.program_id(1)
        page, off = row_page(bt_ref, pos_ref, b, s)
        prev, _ = row_page(bt_ref, pos_ref, jnp.where(s > 0, b, b - 1),
                           jnp.where(s > 0, s - 1, S_new - 1))

        @pl.when((b + s == 0) | (prev != page))
        def _load():
            out_ref[...] = pool_ref[...]

        out_ref[0, pl.ds(off, 1)] = new_ref[0, pl.ds(s, 1)]

    def page_idx(b, s, bt, pos):
        return (row_page(bt, pos, b, s)[0], 0) + zeros

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, S_new),
        in_specs=[
            pl.BlockSpec((1, S_new, *tail), lambda b, s, bt, pos: (b, 0) + zeros),
            pl.BlockSpec((1, ps, *tail), page_idx),
        ],
        out_specs=pl.BlockSpec((1, ps, *tail), page_idx),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        # operand 3 == pool (after the two scalar-prefetch args and new)
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="paged_scatter",
    )(block_table, jnp.asarray(pos, jnp.int32), new.astype(pool.dtype), pool)


def paged_copy_pallas(pool: jax.Array, src: jax.Array, dst: jax.Array, *,
                      interpret: bool) -> jax.Array:
    """Copy pool pages ``src`` -> ``dst`` ((K,) int32 each): one grid step
    DMAs one whole page, the destination scalar-prefetched like the
    scatter's table. The source pages are MATERIALIZED up front (gathered
    before the aliased in-place write), so a ``dst`` page that reappears as
    a later ``src`` reads the ORIGINAL bits — the same snapshot semantics
    as the jnp twin. Duplicate ``dst`` entries are outside the contract
    (every caller clones into distinct freshly drawn pages)."""
    ps, tail = pool.shape[1], pool.shape[2:]
    zeros = (0,) * len(tail)
    (K,) = src.shape
    srcs = jnp.take(pool, jnp.asarray(src, jnp.int32), axis=0)  # (K, ps, ...)

    def kernel(dst_ref, srcs_ref, pool_ref, out_ref):
        del dst_ref, pool_ref  # routing handled by the index maps
        out_ref[0] = srcs_ref[0]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(K,),
        in_specs=[
            pl.BlockSpec((1, ps, *tail), lambda k, d: (k, 0) + zeros),
            # the aliased pool rides along untouched (dummy block)
            pl.BlockSpec((1, ps, *tail), lambda k, d: (0, 0) + zeros),
        ],
        out_specs=pl.BlockSpec((1, ps, *tail), lambda k, d: (d[k], 0) + zeros),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        # operand 2 == pool (after the scalar-prefetch arg and srcs)
        input_output_aliases={2: 0},
        interpret=interpret,
        name="paged_copy",
    )(jnp.asarray(dst, jnp.int32), srcs, pool)


def paged_copy_ref(pool: jax.Array, src: jax.Array, dst: jax.Array) -> jax.Array:
    """jnp twin: advanced-index page copy. ``pool[src]`` is materialized
    before the set, so src/dst overlap reads the ORIGINAL pages; duplicate
    ``dst`` entries are outside the contract (order unspecified)."""
    return pool.at[jnp.asarray(dst, jnp.int32)].set(
        pool[jnp.asarray(src, jnp.int32)])


def paged_scatter_ref(pool: jax.Array, new: jax.Array, pos: jax.Array,
                      block_table: jax.Array) -> jax.Array:
    """jnp twin: advanced-index scatter. Out-of-table block indices read as
    the scratch page (mode="fill", fill 0), so overflow writes are trash-
    binned exactly like the dense path's scatter-with-drop."""
    ps = pool.shape[1]
    B, S_new = new.shape[:2]
    idx = jnp.asarray(pos, jnp.int32)[:, None] + jnp.arange(S_new, dtype=jnp.int32)[None]
    page = block_table.at[jnp.arange(B)[:, None], idx // ps].get(
        mode="fill", fill_value=0)
    return pool.at[page, idx % ps].set(new.astype(pool.dtype))
