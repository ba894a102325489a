"""Architecture assembly: one ArchConfig -> init / forward / decode for all
assigned families (dense, moe, mla_moe, hybrid, rwkv, encdec, vlm).

Homogeneous layer stacks are SCAN-STACKED (params stacked on a leading L axis,
jax.lax.scan over layers) so HLO size and compile time stay flat in depth —
essential for the 61-layer/512-device dry-runs on this CPU container, and
standard practice at production scale (MaxText-style).

Every projection routes through core.linear.QuantizedLinear under the active
PrecisionPolicy — the paper's mixed-precision permutation space applied
network-wide.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro import runtime_flags as RF
from repro.core.policy import PrecisionPolicy
from repro.kernels import ops
from repro.models import ssm
from repro.models.attention import (
    AttnCfg,
    MLACfg,
    PackedRows,
    attn_apply,
    attn_init,
    cache_init,
    mla_apply,
    mla_cache_init,
    mla_init,
)
from repro.models.common import NORMS, embed_apply, embed_init
from repro.models.ffn import MLPCfg, MoECfg, mlp_apply, mlp_init, moe_apply, moe_init
from repro.core.linear import linear_apply, linear_init


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str  # dense | moe | mla_moe | hybrid | rwkv | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0  # 0 -> d_model / n_heads
    qkv_bias: bool = False
    window: Optional[int] = None  # SWA
    norm: str = "rms"
    act: str = "silu"
    rope_theta: float = 10_000.0
    rope_pct: float = 1.0  # stablelm: 0.25
    # moe
    n_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0
    n_shared: int = 0
    shared_d_ff: int = 0
    dense_layers: int = 0  # deepseek-v3: first 3 layers dense
    # mla (deepseek)
    mla: bool = False
    q_lora: int = 1536
    kv_lora: int = 512
    d_nope: int = 128
    d_rope: int = 64
    d_v: int = 128
    mtp: bool = False
    # hybrid (zamba2)
    attn_every: int = 0
    ssm_state: int = 0
    # vlm (qwen2-vl)
    mrope_sections: Optional[tuple[int, int, int]] = None
    n_patches: int = 0
    # encdec (whisper)
    enc_layers: int = 0

    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)

    @property
    def subquadratic(self) -> bool:
        """Can this arch serve 500k contexts? (DESIGN.md Sec. 8 skip rule)"""
        return self.family in ("hybrid", "rwkv") or self.window is not None

    @property
    def vocab_padded(self) -> int:
        """Vocab rounded to 256 so embeddings/head shard on any mesh axis
        (argument shardings require exact divisibility; MaxText-style pad)."""
        return -(-self.vocab // 256) * 256

    @property
    def attn_cfg(self) -> AttnCfg:
        return AttnCfg(
            d_model=self.d_model, n_heads=self.n_heads, kv_heads=self.kv_heads,
            head_dim=self.head_dim, qkv_bias=self.qkv_bias, window=self.window,
            rope_theta=self.rope_theta, mrope_sections=self.mrope_sections,
        )

    @property
    def mla_cfg(self) -> MLACfg:
        return MLACfg(d_model=self.d_model, n_heads=self.n_heads,
                      q_lora=self.q_lora, kv_lora=self.kv_lora,
                      d_nope=self.d_nope, d_rope=self.d_rope, d_v=self.d_v,
                      rope_theta=self.rope_theta)

    @property
    def mlp_cfg(self) -> MLPCfg:
        gated = self.act != "gelu"
        return MLPCfg(self.d_model, self.d_ff, self.act, gated=gated)

    @property
    def moe_cfg(self) -> MoECfg:
        return MoECfg(
            d_model=self.d_model, n_experts=self.n_experts, top_k=self.top_k,
            d_ff_expert=self.moe_d_ff or self.d_ff, n_shared=self.n_shared,
            d_ff_shared=self.shared_d_ff, act=self.act,
        )

    @property
    def mamba_cfg(self) -> ssm.Mamba2Cfg:
        return ssm.Mamba2Cfg(d_model=self.d_model, d_state=self.ssm_state or 64)

    @property
    def rwkv_cfg(self) -> ssm.RWKV6Cfg:
        return ssm.RWKV6Cfg(d_model=self.d_model, d_ff=self.d_ff)


# --------------------------------------------------------------- block defs


def _norm_fns(cfg: ArchConfig):
    return NORMS[cfg.norm]


def _block_init(key, cfg: ArchConfig, policy, mode, dtype, *, kind: str) -> dict:
    """One transformer block of the given kind."""
    ninit, _ = _norm_fns(cfg)
    k1, k2, k3 = jax.random.split(key, 3)
    p: dict = {"norm1": ninit(cfg.d_model), "norm2": ninit(cfg.d_model)}
    if kind in ("dense", "moe"):
        p["attn"] = attn_init(k1, cfg.attn_cfg, policy, mode=mode, dtype=dtype)
        if kind == "dense":
            p["mlp"] = mlp_init(k2, cfg.mlp_cfg, policy, mode=mode, dtype=dtype)
        else:
            p["moe"] = moe_init(k2, cfg.moe_cfg, policy, mode=mode, dtype=dtype)
    elif kind == "mla_dense":
        p["attn"] = mla_init(k1, cfg.mla_cfg, policy, mode=mode, dtype=dtype)
        p["mlp"] = mlp_init(
            k2, MLPCfg(cfg.d_model, cfg.d_ff * 9, cfg.act), policy, mode=mode,
            dtype=dtype)  # deepseek dense layers: d_ff 18432 = 9 * 2048
    elif kind == "mla_moe":
        p["attn"] = mla_init(k1, cfg.mla_cfg, policy, mode=mode, dtype=dtype)
        p["moe"] = moe_init(k2, cfg.moe_cfg, policy, mode=mode, dtype=dtype)
    elif kind == "mamba":
        p = {"norm1": ninit(cfg.d_model)}
        p["mixer"] = ssm.mamba2_init(k1, cfg.mamba_cfg, policy, mode=mode, dtype=dtype)
    elif kind == "rwkv":
        p["att"] = ssm.rwkv6_init(k1, cfg.rwkv_cfg, policy, mode=mode, dtype=dtype)
    elif kind == "enc":
        p["attn"] = attn_init(k1, cfg.attn_cfg, policy, mode=mode, dtype=dtype)
        p["mlp"] = mlp_init(k2, cfg.mlp_cfg, policy, mode=mode, dtype=dtype)
    elif kind == "dec":
        p["attn"] = attn_init(k1, cfg.attn_cfg, policy, mode=mode, dtype=dtype)
        p["cross"] = attn_init(k2, cfg.attn_cfg, policy, mode=mode, dtype=dtype)
        p["norm3"] = ninit(cfg.d_model)
        p["mlp"] = mlp_init(k3, cfg.mlp_cfg, policy, mode=mode, dtype=dtype)
    else:
        raise ValueError(kind)
    return p


def _block_apply(params, x, pos, cfg: ArchConfig, policy, *, kind, mode, impl,
                 cache=None, cache_pos=None, cross_kv=None, causal=True,
                 attend_cached=False, block_tables=None, fused_attn=False,
                 packed=None):
    """Returns (x_out, new_cache, aux). ``packed``: the rows of a packed
    mixed step (attention.PackedRows), for GQA blocks."""
    _, nfn = _norm_fns(cfg)
    aux = jnp.zeros((), jnp.float32)
    if kind in ("dense", "moe", "mla_dense", "mla_moe", "enc", "dec"):
        h = nfn(params["norm1"], x)
        if kind.startswith("mla"):
            a, new_cache = mla_apply(params["attn"], h, pos, cfg.mla_cfg, policy,
                                     mode=mode, impl=impl, cache=cache,
                                     cache_pos=cache_pos,
                                     attend_cached=attend_cached,
                                     block_table=block_tables,
                                     fused=fused_attn)
        else:
            sc = None if cache is None else cache.get("self")
            a, sc_new = attn_apply(params["attn"], h, pos, cfg.attn_cfg, policy,
                                   mode=mode, impl=impl, causal=causal,
                                   cache=sc, cache_pos=cache_pos,
                                   attend_cached=attend_cached,
                                   block_table=block_tables,
                                   fused=fused_attn, packed=packed)
            new_cache = cache if cache is None else dict(cache, self=sc_new)
        x = x + a
        if kind == "dec":
            h = nfn(params["norm3"], x)
            ckv = cross_kv if cross_kv is not None else cache["cross"]
            c, _ = attn_apply(params["cross"], h, pos, cfg.attn_cfg, policy,
                              mode=mode, impl=impl, causal=False,
                              kv_override=ckv)
            x = x + c
        h = nfn(params["norm2"], x)
        if kind in ("moe", "mla_moe"):
            m, aux = moe_apply(params["moe"], h, cfg.moe_cfg, policy, mode=mode, impl=impl)
        elif kind == "mla_dense":
            m = mlp_apply(params["mlp"], h,
                          MLPCfg(cfg.d_model, cfg.d_ff * 9, cfg.act), policy,
                          mode=mode, impl=impl)
        else:
            m = mlp_apply(params["mlp"], h, cfg.mlp_cfg, policy, mode=mode, impl=impl)
        return x + m, new_cache, aux
    if kind == "mamba":
        h = nfn(params["norm1"], x)
        m, new_state = ssm.mamba2_apply(params["mixer"], h, cfg.mamba_cfg, policy,
                                        mode=mode, impl=impl, state=cache)
        return x + m, new_state, aux
    if kind == "rwkv":
        h = nfn(params["norm1"], x)
        a, st_att = ssm.rwkv6_time_mix(params["att"], h, cfg.rwkv_cfg, policy,
                                       mode=mode, impl=impl,
                                       state=cache)
        x = x + a
        h = nfn(params["norm2"], x)
        m, st_ffn = ssm.rwkv6_channel_mix(params["att"], h, cfg.rwkv_cfg, policy,
                                          mode=mode, impl=impl, state=cache)
        new_state = None
        if cache is not None or mode == "serve":
            new_state = {**st_att, **st_ffn}
        return x + m, new_state, aux
    raise ValueError(kind)


def _layer_kinds(cfg: ArchConfig) -> list[str]:
    """Per-layer block kind for the main (decoder) stack."""
    if cfg.family == "dense" or cfg.family == "vlm":
        return ["dense"] * cfg.n_layers
    if cfg.family == "moe":
        return ["moe"] * cfg.n_layers
    if cfg.family == "mla_moe":
        return ["mla_dense"] * cfg.dense_layers + ["mla_moe"] * (cfg.n_layers - cfg.dense_layers)
    if cfg.family == "rwkv":
        return ["rwkv"] * cfg.n_layers
    if cfg.family == "hybrid":
        return ["mamba"] * cfg.n_layers  # shared attn handled separately
    if cfg.family == "encdec":
        return ["dec"] * cfg.n_layers
    raise ValueError(cfg.family)


def _scan_groups(cfg: ArchConfig) -> list[tuple[str, int]]:
    """Contiguous (kind, count) groups -> one stacked scan per group."""
    kinds = _layer_kinds(cfg)
    groups: list[tuple[str, int]] = []
    for kd in kinds:
        if groups and groups[-1][0] == kd:
            groups[-1] = (kd, groups[-1][1] + 1)
        else:
            groups.append((kd, 1))
    return groups


# ------------------------------------------------------------------- model


def init_params(key: jax.Array, cfg: ArchConfig, policy: PrecisionPolicy, *,
                mode: str = "train", dtype=jnp.bfloat16) -> dict:
    if mode == "serve":
        # serve-mode params exist only to feed the integer kernels — reject a
        # policy that addresses unregistered cells before allocating anything
        ops.dispatch.ensure_policy_supported(policy)
    ninit, _ = _norm_fns(cfg)
    ke, kh, kb, ks, km = jax.random.split(key, 5)
    params: dict = {
        "embed": embed_init(ke, cfg.vocab_padded, cfg.d_model, dtype=dtype),
        "final_norm": ninit(cfg.d_model),
        "head": linear_init(kh, cfg.d_model, cfg.vocab_padded, policy.of("head"),
                            mode=mode, dtype=dtype),
    }
    blocks = []
    for gi, (kind, count) in enumerate(_scan_groups(cfg)):
        gkey = jax.random.fold_in(kb, gi)
        keys = jax.random.split(gkey, count)
        blocks.append(jax.vmap(
            lambda k: _block_init(k, cfg, policy, mode, dtype, kind=kind)
        )(keys))
    params["blocks"] = blocks
    if cfg.family == "hybrid":
        params["shared_attn"] = _block_init(ks, cfg, policy, mode, dtype, kind="dense")
    if cfg.family == "encdec":
        enc_keys = jax.random.split(ks, cfg.enc_layers)
        params["enc_blocks"] = jax.vmap(
            lambda k: _block_init(k, cfg, policy, mode, dtype, kind="enc")
        )(enc_keys)
        params["enc_norm"] = ninit(cfg.d_model)
    if cfg.family == "vlm":
        params["patch_proj"] = linear_init(ks, cfg.d_model, cfg.d_model,
                                           policy.of("embed"), mode=mode, dtype=dtype)
    if cfg.mtp:
        params["mtp_block"] = _block_init(km, cfg, policy, mode, dtype, kind="mla_dense")
        params["mtp_proj"] = linear_init(jax.random.fold_in(km, 1), 2 * cfg.d_model,
                                         cfg.d_model, policy.of("head"), mode=mode,
                                         dtype=dtype)
        params["mtp_norm"] = ninit(cfg.d_model)
    return params


def _remat_wrap(body, remat_policy: str):
    if remat_policy == "dots":
        return jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(body)


def _run_stack(params, x, pos, cfg: ArchConfig, policy, *, mode, impl,
               caches=None, cache_pos=None, cross_kv=None, causal=True,
               remat: bool = True, remat_policy: str = "full",
               attend_cached: bool = False, block_tables=None,
               fused_attn: bool = False, packed=None):
    """Scan the grouped block stacks. caches: list matching groups (stacked
    leading dim) or None. Returns (x, new_caches, aux_sum).

    ``block_tables`` selects the paged cache layout: group cache leaves are
    (count, n_pages, page_size, ...) pools shared by every slot, and the
    per-slot (B, n_blocks) tables route reads/writes (closed over by the
    scan body — they are layer-invariant)."""
    aux_total = jnp.zeros((), jnp.float32)
    new_caches = []
    shared = params.get("shared_attn")
    attn_every = cfg.attn_every or 0
    layer_idx = 0

    for gi, blk in enumerate(params["blocks"]):
        kind = _scan_groups(cfg)[gi][0]
        count = _scan_groups(cfg)[gi][1]
        g_cache = None if caches is None else caches[gi]

        def body(carry, xs):
            h, auxc = carry
            bp, bc, ckv = xs
            h2, nc, aux = _block_apply(
                bp, h, pos, cfg, policy, kind=kind, mode=mode, impl=impl,
                cache=bc, cache_pos=cache_pos, cross_kv=ckv, causal=causal,
                attend_cached=attend_cached, block_tables=block_tables,
                fused_attn=fused_attn, packed=packed)
            return (h2.astype(h.dtype), auxc + aux), nc

        body_fn = (_remat_wrap(body, remat_policy)
                   if (remat and mode == "train") else body)

        if cfg.family == "hybrid" and shared is not None and attn_every:
            # interleave the SHARED attention block every `attn_every` layers
            new_g_cache_chunks = []
            off = 0
            sub = 0
            while off < count:
                n_sub = min(attn_every, count - off)
                sl = jax.tree.map(lambda a: a[off : off + n_sub], blk)
                cc = None if g_cache is None else jax.tree.map(
                    lambda a: a[off : off + n_sub], g_cache["mamba"])
                (x, aux_total), nc = jax.lax.scan(
                    body_fn, (x, aux_total), (sl, cc, None), unroll=RF.unroll(n_sub))
                if nc is not None:
                    new_g_cache_chunks.append(nc)
                sa_cache = (None if g_cache is None else
                            jax.tree.map(lambda a: a[sub], g_cache["shared"]))
                x, sa_new, _ = _block_apply(
                    shared, x, pos, cfg, policy, kind="dense", mode=mode,
                    impl=impl, cache=sa_cache, cache_pos=cache_pos,
                    attend_cached=attend_cached, fused_attn=fused_attn)
                if sa_new is not None and g_cache is not None:
                    new_g_cache_chunks.append(("shared", sub, sa_new))
                off += n_sub
                sub += 1
            # reassemble hybrid caches
            if g_cache is not None:
                mamba_parts = [c for c in new_g_cache_chunks if not isinstance(c, tuple)]
                shared_parts = [c for c in new_g_cache_chunks if isinstance(c, tuple)]
                mamba_cat = jax.tree.map(lambda *a: jnp.concatenate(a, 0), *mamba_parts)
                shared_st = jax.tree.map(
                    lambda *a: jnp.stack(a, 0), *[c[2] for c in shared_parts])
                new_caches.append({"mamba": mamba_cat, "shared": shared_st})
            else:
                new_caches.append(None)
        else:
            (x, aux_total), nc = jax.lax.scan(
                body_fn, (x, aux_total), (blk, g_cache, cross_kv),
                unroll=RF.unroll(count))
            new_caches.append(nc)
        layer_idx += count
    return x, new_caches, aux_total


def forward(params: dict, batch: dict, cfg: ArchConfig, policy: PrecisionPolicy, *,
            mode: str = "train", impl: ops.Impl = "auto", remat: bool = True,
            remat_policy: str = "full", output: str = "logits"):
    """Full-sequence forward (train / eval / prefill-style). Returns
    (logits (B, S, V), aux dict); with output="hidden", returns the
    final-norm hidden states instead (the loss applies the head in chunks —
    (B, S, V) logits are never materialized; see train.step.chunked_ce)."""
    _, nfn = _norm_fns(cfg)
    aux: dict[str, Any] = {}

    if cfg.family == "encdec":
        frames = batch["frames"].astype(jnp.bfloat16)  # (B, S_enc, d) stub frontend
        enc_pos = jnp.broadcast_to(
            jnp.arange(frames.shape[1])[None], frames.shape[:2])

        def enc_body(h, bp):
            h2, _, _ = _block_apply(bp, h, enc_pos, cfg, policy, kind="enc",
                                    mode=mode, impl=impl, causal=False)
            return h2.astype(h.dtype), None

        enc_h, _ = jax.lax.scan(enc_body, frames, params["enc_blocks"],
                                unroll=RF.unroll(cfg.enc_layers))
        enc_h = nfn(params["enc_norm"], enc_h)
        # cross K/V are recomputed per decoder layer inside the stack via
        # kv_override; here we pass raw encoder states and let each layer
        # project them (weights differ per layer).
        x = embed_apply(params["embed"], batch["tokens"]).astype(jnp.bfloat16)
        pos = jnp.broadcast_to(jnp.arange(x.shape[1])[None], x.shape[:2])
        cross = _encdec_cross_kv(params, enc_h, cfg, policy, mode=mode, impl=impl)
        x, _, aux_moe = _run_stack(params, x, pos, cfg, policy, mode=mode,
                                   impl=impl, cross_kv=cross, remat=remat,
                                   remat_policy=remat_policy)
    else:
        tokens = batch["tokens"]
        x = embed_apply(params["embed"], tokens).astype(jnp.bfloat16)
        if cfg.family == "vlm":
            patches = batch["patches"].astype(jnp.bfloat16)
            patches = linear_apply(params["patch_proj"], patches,
                                   policy.of("embed"), mode=mode, impl=impl)
            x = jax.lax.dynamic_update_slice_in_dim(x, patches, 0, 1)
            pos = batch["positions"]  # (3, B, S) M-RoPE
        else:
            pos = jnp.broadcast_to(jnp.arange(x.shape[1])[None], x.shape[:2])
        x, _, aux_moe = _run_stack(params, x, pos, cfg, policy, mode=mode,
                                   impl=impl, remat=remat,
                                   remat_policy=remat_policy)

    x = nfn(params["final_norm"], x)
    aux["moe_aux"] = aux_moe

    if cfg.mtp and mode == "train":
        # DeepSeek-V3 multi-token prediction (depth 1): predict t+2 from
        # [h_t ; emb(token_{t+1})].
        emb_next = embed_apply(params["embed"], batch["tokens"]).astype(jnp.bfloat16)
        emb_next = jnp.roll(emb_next, -1, axis=1)
        _, nfn2 = _norm_fns(cfg)
        merged = jnp.concatenate([nfn2(params["mtp_norm"], x), emb_next], axis=-1)
        h_mtp = linear_apply(params["mtp_proj"], merged, policy.of("head"),
                             mode=mode, impl=impl)
        pos_m = jnp.broadcast_to(jnp.arange(x.shape[1])[None], x.shape[:2])
        h_mtp, _, _ = _block_apply(params["mtp_block"], h_mtp, pos_m, cfg, policy,
                                   kind="mla_dense", mode=mode, impl=impl)
        if output == "hidden":
            aux["mtp_hidden"] = h_mtp
        else:
            aux["mtp_logits"] = linear_apply(params["head"], h_mtp,
                                             policy.of("head"), mode=mode, impl=impl)
    if output == "hidden":
        return x, aux
    logits = linear_apply(params["head"], x, policy.of("head"), mode=mode, impl=impl)
    return logits, aux


def _encdec_cross_kv(params, enc_h, cfg, policy, *, mode, impl):
    """Per-decoder-layer projected encoder K/V, stacked (L, B, S, H, D)."""
    lp = policy.of("attn_qkv")

    def proj(bp):
        k = linear_apply(bp["cross"]["wk"], enc_h, lp, mode=mode, impl=impl)
        v = linear_apply(bp["cross"]["wv"], enc_h, lp, mode=mode, impl=impl)
        B, S, _ = enc_h.shape
        return (k.reshape(B, S, cfg.kv_heads, cfg.head_dim),
                v.reshape(B, S, cfg.kv_heads, cfg.head_dim))

    _, kv = jax.lax.scan(lambda c, bp: (c, proj(bp)), None, params["blocks"][0],
                         unroll=RF.unroll(cfg.n_layers))
    return kv


# --------------------------------------------------------------- decoding


def init_cache(cfg: ArchConfig, policy: PrecisionPolicy, batch: int, s_max: int,
               *, enc_len: int = 0) -> list:
    """Per-scan-group stacked caches."""
    bits = policy.kv_cache_bits
    caches = []
    for kind, count in _scan_groups(cfg):
        if kind in ("dense", "moe"):
            one = {"self": cache_init(batch, s_max, cfg.kv_heads, cfg.head_dim, bits)}
        elif kind.startswith("mla"):
            one = mla_cache_init(batch, s_max, cfg.mla_cfg, bits)
        elif kind == "mamba":
            one = ssm.mamba2_state_init(batch, cfg.mamba_cfg)
        elif kind == "rwkv":
            one = ssm.rwkv6_state_init(batch, cfg.rwkv_cfg)
        elif kind == "dec":
            one = {
                "self": cache_init(batch, s_max, cfg.kv_heads, cfg.head_dim, bits),
                "cross": (
                    jnp.zeros((batch, enc_len, cfg.kv_heads, cfg.head_dim), jnp.bfloat16),
                    jnp.zeros((batch, enc_len, cfg.kv_heads, cfg.head_dim), jnp.bfloat16),
                ),
            }
        else:
            raise ValueError(kind)
        stacked = jax.tree.map(lambda a: jnp.broadcast_to(a, (count,) + a.shape), one)
        if cfg.family == "hybrid":
            n_apps = -(-count // cfg.attn_every)
            sa = {"self": cache_init(batch, s_max, cfg.kv_heads, cfg.head_dim, bits)}
            sa = jax.tree.map(lambda a: jnp.broadcast_to(a, (n_apps,) + a.shape), sa)
            stacked = {"mamba": stacked, "shared": sa}
        caches.append(stacked)
    return caches


def prefill_step(params: dict, batch: dict, caches: list, cfg: ArchConfig,
                 policy: PrecisionPolicy, *, impl: ops.Impl = "auto"):
    """Serve-side prefill: full-prompt forward that WRITES the quantized KV
    cache (flash attention over the fresh k/v) and returns last-token logits
    only — never materializing (B, S, V). Returns (logits (B,1,V), caches)."""
    _, nfn = _norm_fns(cfg)
    mode = "serve"
    if cfg.family == "encdec":
        # encoder + cross-KV cache fill, then decoder prefill
        raise NotImplementedError("whisper prefill lowers via forward(); see engine")
    tokens = batch["tokens"]
    x = embed_apply(params["embed"], tokens).astype(jnp.bfloat16)
    B, S = tokens.shape
    if cfg.family == "vlm":
        patches = batch["patches"].astype(jnp.bfloat16)
        patches = linear_apply(params["patch_proj"], patches, policy.of("embed"),
                               mode=mode, impl=impl)
        x = jax.lax.dynamic_update_slice_in_dim(x, patches, 0, 1)
        pos_ids = batch["positions"]
    else:
        pos_ids = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    x, new_caches, _ = _run_stack(params, x, pos_ids, cfg, policy, mode=mode,
                                  impl=impl, caches=caches,
                                  cache_pos=jnp.int32(0), remat=False)
    x_last = nfn(params["final_norm"], x[:, -1:])
    logits = linear_apply(params["head"], x_last, policy.of("head"), mode=mode,
                          impl=impl)
    return logits, new_caches


def decode_step(params: dict, tokens: jax.Array, pos: jax.Array, caches: list,
                cfg: ArchConfig, policy: PrecisionPolicy, *,
                impl: ops.Impl = "auto",
                block_tables: Optional[jax.Array] = None,
                fused_attn: bool = False):
    """One serving step: tokens (B, S_new=1), pos = cache write position —
    scalar int32 (lockstep batch) or (B,) int32 (continuous batching, one
    offset per slot). Returns (logits (B, S_new, V), new_caches).

    ``block_tables`` (B, n_blocks) switches the cache to the paged pool
    layout (see init_paged_cache; the page size is each pool leaf's axis 2):
    attention gathers each slot's pages into the same logical rows the
    dense layout stores and scatters the new token's K/V through the table
    — decoded tokens are bit-identical to the dense-slot path.

    ``fused_attn`` routes attention through the fused paged-attention
    kernel (kernels/paged_attn.py): no gather-to-dense materialization;
    quantized KV pages are dequantized inside the kernel. Works with dense
    AND paged caches (the dense layout is viewed as pages); numerics match
    the default path to ulp-level (page-blocked softmax reduction order)."""
    _, nfn = _norm_fns(cfg)
    mode = "serve"
    x = embed_apply(params["embed"], tokens).astype(jnp.bfloat16)
    B, S = tokens.shape
    pos_b = jnp.broadcast_to(jnp.atleast_1d(pos), (B,))
    pos_ids = pos_b[:, None] + jnp.arange(S)[None]
    if cfg.mrope_sections is not None:
        pos_ids = jnp.broadcast_to(pos_ids[None], (3, B, S))
    x, new_caches, _ = _run_stack(params, x, pos_ids, cfg, policy, mode=mode,
                                  impl=impl, caches=caches, cache_pos=pos,
                                  remat=False, block_tables=block_tables,
                                  fused_attn=fused_attn)
    x = nfn(params["final_norm"], x)
    logits = linear_apply(params["head"], x, policy.of("head"), mode=mode, impl=impl)
    return logits, new_caches


def sample_tokens(logits: jax.Array, temps: jax.Array, top_k: jax.Array,
                  top_p: jax.Array, seeds: jax.Array,
                  counters: jax.Array) -> jax.Array:
    """THE batched per-slot sampler — every token the serving engine emits
    comes through here, whether from a decode step's logits or a prefill's
    last-token logits (the engine fuses this into its jitted decode so the
    hot loop stays a single jit; the prefill call traces once at B=1).

    ``logits`` is (B, V); the per-slot vectors are (B,): ``temps`` f32
    (0 => greedy argmax, bit-identical to the pre-sampler engines),
    ``top_k`` i32 (0 => off), ``top_p`` f32 (1.0 => off), ``seeds`` u32,
    ``counters`` i32 (tokens already emitted for the slot's request).

    The PRNG is counter-based: token i of a request draws from
    ``fold_in(PRNGKey(seed), i)`` — a pure function of (seed, i), so a
    request's stream is independent of its slot, its batch neighbors, the
    cache backend, and the kernel impl (jnp and pallas produce bit-equal
    logits, so equal samples). Returns (B,) int32 token ids.
    """
    logits = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def one(lg, t, k, p, seed, ctr):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), ctr)
        lg = lg / jnp.maximum(t, 1e-6)
        # ONE descending sort serves both truncations: top-k masking only
        # sends sub-threshold entries to -inf / probability zero, so the
        # pre-mask order is still a valid descending order of the masked
        # distribution (every kept entry precedes every masked one)
        order = jnp.argsort(-lg)
        # top-k: keep logits >= the k-th largest (ties included; k<=0 off)
        kth = lg[order[jnp.clip(k - 1, 0, lg.shape[0] - 1)]]
        lg = jnp.where((k > 0) & (lg < kth), -jnp.inf, lg)
        # top-p nucleus over the post-top-k distribution: keep the smallest
        # descending-probability set whose mass reaches p (the first token
        # is always kept: its preceding cumulative mass is 0 < p)
        probs = jax.nn.softmax(lg)
        sp = probs[order]
        keep_sorted = (jnp.cumsum(sp) - sp) < p
        keep = jnp.zeros_like(keep_sorted).at[order].set(keep_sorted)
        lg = jnp.where(keep, lg, -jnp.inf)
        return jax.random.categorical(key, lg).astype(jnp.int32)

    def stochastic(_):
        sampled = jax.vmap(one)(logits, temps, top_k, top_p, seeds, counters)
        # greedy lanes in a mixed batch keep their argmax (idle decode
        # lanes ride here too: their temp is 0 and their token is discarded)
        return jnp.where(temps > 0, sampled, greedy)

    # runtime branch, not jnp.where: an all-greedy step (the default-params
    # serving path, every lane idle or temp=0) must not pay the stochastic
    # lane's O(B * V log V) sorts + categorical just to discard the result —
    # lax.cond executes exactly one side
    return jax.lax.cond(jnp.any(temps > 0), stochastic, lambda _: greedy,
                        operand=None)


#: Families whose caches are pure position-indexed KV stores — safe for
#: batched/chunked prefill (right-padded chunk tails are masked out and later
#: overwritten). Recurrent-state families (hybrid/rwkv) fold every token into
#: the state unconditionally, so they must prefill token-by-token; encdec/vlm
#: prefill needs the encoder/patch side-inputs forward() handles.
PREFILL_CHUNKABLE_FAMILIES = ("dense", "moe", "mla_moe")

#: Families whose caches can live in a paged page pool: every cache leaf is
#: a position-indexed KV (or MLA latent) store, so "token row" is the unit
#: of storage and pages are interchangeable. Recurrent-state families
#: (hybrid/rwkv) carry O(1) per-slot state with no sequence axis — there is
#: no paged analogue, they keep the dense-slot layout; encdec additionally
#: owns a batch-indexed cross-attention cache.
PAGEABLE_FAMILIES = ("dense", "moe", "mla_moe", "vlm")


def init_paged_cache(cfg: ArchConfig, policy: PrecisionPolicy, n_pages: int,
                     page_size: int) -> list:
    """Paged KV pool: the same per-scan-group stacked trees as
    :func:`init_cache`, with the (batch, s_max) slot stripes replaced by a
    global (n_pages, page_size) page pool on every leaf — a page is
    ``page_size`` token rows of quantized/packed K/V, assignable to any slot
    via a block table. Page 0 is reserved by the serving cache manager as
    the scratch page (unallocated block-table entries point at it)."""
    if cfg.family not in PAGEABLE_FAMILIES:
        raise NotImplementedError(
            f"paged KV cache unsupported for family {cfg.family!r} "
            f"(pageable: {PAGEABLE_FAMILIES}) — recurrent state has no "
            f"token-row unit to page")
    return init_cache(cfg, policy, n_pages, page_size)


def prefill_chunk(params: dict, tokens: jax.Array, pos: jax.Array, caches: list,
                  cfg: ArchConfig, policy: PrecisionPolicy, *,
                  last_idx: Optional[jax.Array] = None,
                  head: bool = True,
                  impl: ops.Impl = "auto"):
    """Batched prefill of one token chunk: tokens (B, S_chunk) are written to
    the quantized KV cache at ``pos`` ((B,) or scalar int32) in ONE forward,
    attending through the cache (``attend_cached``) so chunks after the first
    see earlier context — numerically the decode path, batched over S.

    Returns (last-token logits (B, 1, V), new_caches); (B, S, V) is never
    materialized. ``last_idx`` picks which chunk position is "last" (int32,
    default S-1) so a right-padded final chunk can report the logits of the
    final *real* token. Padded tail positions write k/v the causal mask hides
    (the families in PREFILL_CHUNKABLE_FAMILIES have pure position-indexed
    caches); :func:`prefill_into_slot` scrubs those rows so the cache state
    is bit-identical to an unpadded prefill.

    ``head=False`` (static) skips final-norm + the vocab head entirely and
    returns ``(None, new_caches)`` — non-final chunks of a long prompt only
    exist to fill the cache, so they never pay the head matmul.
    """
    if cfg.family not in PREFILL_CHUNKABLE_FAMILIES:
        raise NotImplementedError(
            f"chunked prefill unsupported for family {cfg.family!r}; "
            f"step token-by-token via decode_step instead")
    _, nfn = _norm_fns(cfg)
    mode = "serve"
    x = embed_apply(params["embed"], tokens).astype(jnp.bfloat16)
    B, S = tokens.shape
    pos_b = jnp.broadcast_to(jnp.atleast_1d(pos), (B,))
    pos_ids = pos_b[:, None] + jnp.arange(S)[None]
    x, new_caches, _ = _run_stack(params, x, pos_ids, cfg, policy, mode=mode,
                                  impl=impl, caches=caches, cache_pos=pos,
                                  remat=False, attend_cached=True)
    if not head:
        return None, new_caches
    if last_idx is None:
        last_idx = jnp.int32(S - 1)
    x_last = jax.lax.dynamic_slice_in_dim(x, last_idx, 1, axis=1)
    x_last = nfn(params["final_norm"], x_last)
    logits = linear_apply(params["head"], x_last, policy.of("head"), mode=mode,
                          impl=impl)
    return logits, new_caches


def prefill_into_slot(params: dict, tokens: jax.Array, slot: jax.Array,
                      pos: jax.Array, caches: list, cfg: ArchConfig,
                      policy: PrecisionPolicy, *,
                      last_idx: Optional[jax.Array] = None,
                      head: bool = True,
                      impl: ops.Impl = "auto"):
    """Single-slot prefill against an ``n_slots``-batch cache: slice cache row
    ``slot``, run :func:`prefill_chunk` at B=1, scatter the row back. slot /
    pos / last_idx are all traced int32, so one jitted trace serves every
    (slot, position, chunk-fill) combination — compute is O(1 slot), not
    O(n_slots) like stepping the whole decode batch per prompt token.

    Cache leaves are stacked (n_groups list of (count, n_slots, ...) trees);
    the slot axis is axis 1 everywhere. Returns (logits (1, 1, V), caches).
    """
    row = jax.tree.map(lambda a: jax.lax.dynamic_slice_in_dim(a, slot, 1, 1),
                       caches)
    # (1,) vector pos => seq_insert takes the scatter path, whose out-of-range
    # writes DROP (a right-padded chunk near s_max must not clamp-shift onto
    # real cache rows the way dynamic_update_slice would).
    pos_v = jnp.reshape(pos, (1,)).astype(jnp.int32)
    logits, row = prefill_chunk(params, tokens, pos_v, row, cfg, policy,
                                last_idx=last_idx, head=head, impl=impl)
    if last_idx is not None:
        # Scrub the right-padded tail of a final chunk: the rows it wrote are
        # causally masked anyway, but zeroing them makes chunked prefill
        # bit-identical to an unpadded whole-prompt prefill (and keeps the
        # "no stale K/V" cache-manager guarantee). Real rows get an
        # out-of-range index, which scatter-with-drop ignores; every cache
        # leaf of a chunkable family is (count, B, s_max, ...).
        S = tokens.shape[1]
        row_idx = jnp.reshape(pos, ()) + jnp.arange(S, dtype=jnp.int32)
        scrub_idx = jnp.where(jnp.arange(S) > last_idx, row_idx,
                              jnp.int32(2**30))
        row = jax.tree.map(
            lambda a: a.at[:, :, scrub_idx].set(jnp.zeros((), a.dtype),
                                                mode="drop"),
            row)
    new_caches = jax.tree.map(
        lambda full, r: jax.lax.dynamic_update_slice_in_dim(full, r, slot, 1),
        caches, row)
    return logits, new_caches


def prefill_into_pages(params: dict, tokens: jax.Array, block_row: jax.Array,
                       pos: jax.Array, caches: list, cfg: ArchConfig,
                       policy: PrecisionPolicy, *, page_size: int,
                       last_idx: Optional[jax.Array] = None,
                       head: bool = True,
                       impl: ops.Impl = "auto"):
    """Paged twin of :func:`prefill_into_slot`: chunk-prefill one request
    whose cache rows live in a page pool. ``block_row`` is the request's
    (n_blocks,) block table (traced int32; unallocated entries point at the
    scratch page 0). The request's pages are gathered into one contiguous
    (1, n_blocks * page_size, ...) logical row, :func:`prefill_chunk` runs
    exactly as on the dense layout (so chunked-paged prefill is bit-
    identical to chunked-dense), and the row is scattered back page by
    page. Pad-scrub rows and the row's unwritten tail land back on the
    pages they came from; blocks still mapping to the scratch page just
    rewrite trash.

    Cache leaves are (count, n_pages, page_size, ...); returns
    (logits (1, 1, V), caches)."""
    nb = block_row.shape[0]

    def gather_row(a):
        g = jnp.take(a, block_row, axis=1)  # (count, nb, ps, ...)
        return g.reshape(a.shape[0], 1, nb * page_size, *a.shape[3:])

    row = jax.tree.map(gather_row, caches)
    # (1,) vector pos => scatter path with drop semantics, as in
    # prefill_into_slot (right-padded chunks near capacity must not clamp)
    pos_v = jnp.reshape(pos, (1,)).astype(jnp.int32)
    logits, row = prefill_chunk(params, tokens, pos_v, row, cfg, policy,
                                last_idx=last_idx, head=head, impl=impl)
    if last_idx is not None:
        # same pad scrub as prefill_into_slot: chunked == whole, bit for bit
        S = tokens.shape[1]
        row_idx = jnp.reshape(pos, ()) + jnp.arange(S, dtype=jnp.int32)
        scrub_idx = jnp.where(jnp.arange(S) > last_idx, row_idx,
                              jnp.int32(2**30))
        row = jax.tree.map(
            lambda a: a.at[:, :, scrub_idx].set(jnp.zeros((), a.dtype),
                                                mode="drop"),
            row)

    def scatter_row(full, r):
        r = r.reshape(full.shape[0], nb, page_size, *full.shape[3:])
        return full.at[:, block_row].set(r)

    new_caches = jax.tree.map(scatter_row, caches, row)
    return logits, new_caches


#: Families whose mixed steps run packed (:func:`mixed_step`). ``moe`` and
#: ``mla_moe`` stay on :func:`mixed_step_padded`: ``moe_apply`` sizes expert
#: capacity from the step's token count, so packing would change their
#: numbers, and MLA's absorbed attention has no packed path yet.
PACKED_FAMILIES = ("dense",)


def mixed_step(params: dict, tokens: jax.Array, pos: jax.Array,
               lanes: jax.Array, caches: list, cfg: ArchConfig,
               policy: PrecisionPolicy, *, emit: jax.Array,
               starts: jax.Array, impl: ops.Impl = "auto",
               block_tables: Optional[jax.Array] = None):
    """One continuous-batching step over a PACKED token batch: only rows
    that can carry a token are computed, ``T = budget + n_slots`` of them,
    where the padded layout (:func:`mixed_step_padded`) computes
    ``n_slots x budget``. A long prompt rides the decode batch a chunk at
    a time (Sarathi-style chunked piggybacking) without monopolizing the
    device between decode steps.

    Layout (``tokens``, ``pos``, ``lanes`` all (T,) int32):

    - rows ``[0, budget)``: this step's prefill chunks, each contiguous, in
      allotment order, right-padded; ``starts`` (P,) holds each chunk's
      first row, ascending, and ``budget`` for blocks the step leaves
      empty (P is static: the most chunks a step carries);
    - rows ``[budget, T)``: the decode rows, row ``budget + s`` slot s's;
    - ``lanes[t]`` is row t's slot, ``pos[t]`` its cache write position
      (and RoPE position). A pad row — the unused prefill tail, the decode
      row of an idle or still-prefilling slot — has ``pos`` ``PAD_POS``:
      a dense write drops, a paged one lands in the scratch page 0, so pad
      rows never touch a real cache row and nothing needs scrubbing.
    - ``emit`` (n_slots,): the row whose logits slot s returns (its final
      chunk's last row, or its decode row).

    Row-wise work (embed, every quantized linear, norms, RoPE, SwiGLU) runs
    on the T rows; activation quantisation clips at the static PACT beta,
    so rows are independent and packing changes no row's numbers.
    Attention (``attention.packed_attend``): decode rows attend exactly as
    :func:`decode_step` with ``fused_attn=False`` does; each prefill chunk
    attends as one (budget,) query block over its slot's table only — the
    cached-attention math of :func:`prefill_chunk`. So token streams stay
    bit-equal to the serialized engine's with ``fused_attn=False`` (greedy
    streams also equal its fused decode's, as in the padded step).
    ``block_tables`` (n_slots, n_blocks) selects the paged pool layout
    (page size: the pool's axis 2).

    Only :data:`PACKED_FAMILIES` run packed. Returns (logits (n_slots, 1,
    V), new_caches); rows of idle slots return garbage the caller discards.
    """
    if cfg.family not in PACKED_FAMILIES:
        raise NotImplementedError(
            f"packed mixed steps unsupported for family {cfg.family!r} "
            f"(packed: {PACKED_FAMILIES}); use mixed_step_padded")
    _, nfn = _norm_fns(cfg)
    mode = "serve"
    T = tokens.shape[0]
    rows = PackedRows(lane=jnp.asarray(lanes, jnp.int32),
                      pos=jnp.asarray(pos, jnp.int32),
                      starts=jnp.asarray(starts, jnp.int32),
                      budget=T - emit.shape[0])
    x = embed_apply(params["embed"], tokens[:, None]).astype(jnp.bfloat16)
    x, new_caches, _ = _run_stack(params, x, rows.pos[:, None], cfg, policy,
                                  mode=mode, impl=impl, caches=caches,
                                  remat=False, block_tables=block_tables,
                                  packed=rows)
    x_last = nfn(params["final_norm"], x[emit])
    logits = linear_apply(params["head"], x_last, policy.of("head"), mode=mode,
                          impl=impl)
    return logits, new_caches


def mixed_step_padded(params: dict, tokens: jax.Array, pos: jax.Array,
                      n_real: jax.Array, caches: list, cfg: ArchConfig,
                      policy: PrecisionPolicy, *,
                      impl: ops.Impl = "auto",
                      block_tables: Optional[jax.Array] = None,
                      page_size: Optional[int] = None):
    """The PADDED mixed step, for the chunkable families outside
    :data:`PACKED_FAMILIES` (``moe``, ``mla_moe``): every lane of the (B, W)
    token batch is either a DECODE lane (``n_real[b] == 1``: its next
    single token), a PREFILL lane (``n_real[b]`` up to W: a right-padded
    chunk of its prompt), or idle (``n_real[b] == 0``), and all B x W rows
    are computed.

    The whole batch attends through the cache (``attend_cached``, the same
    branch :func:`prefill_chunk` uses), so a decode lane here is numerically
    identical to :func:`decode_step` at S=1 batched over W causally-masked
    positions — lanes are row-independent through embed/attention/MLP/head,
    which is what makes mixed-step token streams bit-equal to the serialized
    engine's. ``pos`` is (B,) int32 per-lane write positions.

    Returns (logits (B, 1, V), new_caches): lane b's logits are taken at its
    last REAL position (``n_real[b] - 1``), so a prefill lane's final chunk
    yields exactly the last-prompt-token logits the serialized prefill
    returns, and a decode lane yields its position-0 logits. Idle lanes
    (n_real 0) return garbage the caller discards.

    After the forward, each lane's padded tail rows (chunk positions >=
    n_real[b]) are scrubbed to zero, so the cache state is bit-identical to
    the serialized engine's after the same logical writes — dense leaves
    (count, B, s_max, ...) scrub in place; paged leaves (count, n_pages,
    page_size, ...) scrub through ``block_tables`` (required then, with the
    pool's static ``page_size``). Rows past a lane's table (or mapped to the
    scratch page 0) are left alone — the scratch page is trash by contract.

    No ``fused_attn`` parameter: the fused decode kernel requires S == 1 and
    a mixed step is S == W > 1, so it always takes the unfused cache-read
    branch. Greedy lanes are unaffected (the PR-6 bench gate proves fused
    and unfused argmax-equal); engines mixing fused serialized steps with
    mixed steps under stochastic sampling should pin ``fused_attn=False``.
    """
    if cfg.family not in PREFILL_CHUNKABLE_FAMILIES:
        raise NotImplementedError(
            f"mixed prefill+decode steps unsupported for family "
            f"{cfg.family!r} (supported: {PREFILL_CHUNKABLE_FAMILIES}); "
            f"serve serialized via decode_step/prefill instead")
    if block_tables is not None and page_size is None:
        raise ValueError("page_size is required with block_tables")
    _, nfn = _norm_fns(cfg)
    mode = "serve"
    x = embed_apply(params["embed"], tokens).astype(jnp.bfloat16)
    B, W = tokens.shape
    pos = jnp.asarray(pos, jnp.int32)
    n_real = jnp.asarray(n_real, jnp.int32)
    pos_ids = pos[:, None] + jnp.arange(W, dtype=jnp.int32)[None]
    x, new_caches, _ = _run_stack(params, x, pos_ids, cfg, policy, mode=mode,
                                  impl=impl, caches=caches, cache_pos=pos,
                                  remat=False, attend_cached=True,
                                  block_tables=block_tables)
    # per-lane last REAL position -> (B, 1, d) before the head matmul, so
    # the vocab projection is O(B), never O(B * W)
    last_idx = jnp.maximum(n_real - 1, 0)
    x_last = jnp.take_along_axis(x, last_idx[:, None, None], axis=1)
    x_last = nfn(params["final_norm"], x_last)
    logits = linear_apply(params["head"], x_last, policy.of("head"), mode=mode,
                          impl=impl)

    # per-lane pad scrub: zero every row this step wrote beyond the lane's
    # real tokens (same invariant as prefill_into_slot/_pages — "no stale
    # K/V", and cache bytes bit-identical to the serialized engine's)
    row_idx = pos[:, None] + jnp.arange(W, dtype=jnp.int32)[None]   # (B, W)
    pad = jnp.arange(W, dtype=jnp.int32)[None] >= n_real[:, None]   # (B, W)
    if block_tables is None:
        scrub_idx = jnp.where(pad, row_idx, jnp.int32(2**30))
        b_ix = jnp.arange(B, dtype=jnp.int32)[:, None]
        new_caches = jax.tree.map(
            lambda a: a.at[:, b_ix, scrub_idx].set(jnp.zeros((), a.dtype),
                                                   mode="drop"),
            new_caches)
    else:
        nb = block_tables.shape[1]
        blk = row_idx // page_size
        off = row_idx % page_size
        page = jnp.take_along_axis(block_tables, jnp.minimum(blk, nb - 1),
                                   axis=1)
        # scrub only pad rows that map to a real allocated page; rows past
        # the lane's table or binned to the scratch page stay trash
        page = jnp.where(pad & (blk < nb) & (page != 0), page,
                         jnp.int32(2**30))
        new_caches = jax.tree.map(
            lambda a: a.at[:, page, off].set(jnp.zeros((), a.dtype),
                                             mode="drop"),
            new_caches)
    return logits, new_caches


def spec_verify_step(params: dict, tokens: jax.Array, pos: jax.Array,
                     n_real: jax.Array, temps: jax.Array, top_ks: jax.Array,
                     top_ps: jax.Array, seeds: jax.Array, counters: jax.Array,
                     caches: list, cfg: ArchConfig, policy: PrecisionPolicy, *,
                     impl: ops.Impl = "auto",
                     block_tables: Optional[jax.Array] = None,
                     page_size: Optional[int] = None):
    """Speculative-decoding VERIFY: the target model scores a whole drafted
    window in ONE jitted call. Lane b of ``tokens`` (B, W = k+1) is
    ``[last_emitted, draft_0, .., draft_{k-1}]`` for a speculating lane
    (``n_real[b] == W``), a plain right-padded 1-token decode lane
    (``n_real[b] == 1``), or idle (0). The forward is :func:`mixed_step_padded`'s
    (``attend_cached`` through the shared cache, per-lane pad scrub after),
    with two differences:

    - the head runs over ALL W positions (W is small — k+1, not a prefill
      chunk), because verification needs a target token at every offset;
    - sampling is fused in-jit through :func:`sample_tokens`'s counter-based
      PRNG: offset j of lane b draws at counter ``counters[b] + j`` — the
      exact (seed, counter) cell the serialized engine would use for that
      emission index, which is what makes accepted streams bit-identical to
      the non-speculative engine (greedy AND seeded) on every backend.

    Returns (targets (B, W) int32, new_caches). The caller accepts the
    longest prefix where draft_j == targets[:, j] host-side, emits
    ``targets[:, 0..m]`` (the bonus token rides at the first mismatch), and
    rolls back rejected rows via the cache-manager ``truncate`` verb.
    Pad/idle offsets return garbage tokens the caller never reads.
    """
    if cfg.family not in PREFILL_CHUNKABLE_FAMILIES:
        raise NotImplementedError(
            f"speculative verify unsupported for family {cfg.family!r} "
            f"(supported: {PREFILL_CHUNKABLE_FAMILIES}); these families "
            f"lack the position-indexed cache the multi-token write needs")
    if block_tables is not None and page_size is None:
        raise ValueError("page_size is required with block_tables")
    _, nfn = _norm_fns(cfg)
    mode = "serve"
    x = embed_apply(params["embed"], tokens).astype(jnp.bfloat16)
    B, W = tokens.shape
    pos = jnp.asarray(pos, jnp.int32)
    n_real = jnp.asarray(n_real, jnp.int32)
    pos_ids = pos[:, None] + jnp.arange(W, dtype=jnp.int32)[None]
    x, new_caches, _ = _run_stack(params, x, pos_ids, cfg, policy, mode=mode,
                                  impl=impl, caches=caches, cache_pos=pos,
                                  remat=False, attend_cached=True,
                                  block_tables=block_tables)
    x = nfn(params["final_norm"], x)
    logits = linear_apply(params["head"], x, policy.of("head"), mode=mode,
                          impl=impl)                              # (B, W, V)

    # fused rejection sampling: offset j of lane b is emission index
    # counters[b] + j of its request — flatten to (B*W,) lanes and let the
    # batched sampler draw every candidate from its own counter cell
    flat = logits.reshape(B * W, -1)
    ctr = (counters[:, None] + jnp.arange(W, dtype=jnp.int32)[None])
    targets = sample_tokens(
        flat, jnp.repeat(temps, W), jnp.repeat(top_ks, W),
        jnp.repeat(top_ps, W), jnp.repeat(seeds, W),
        ctr.reshape(-1)).reshape(B, W)

    # per-lane pad scrub, verbatim from mixed_step_padded: no stale K/V beyond a
    # lane's real rows (rejected rows are rolled back by truncate, which
    # scrubs separately — this handles pad lanes and idle lanes)
    row_idx = pos[:, None] + jnp.arange(W, dtype=jnp.int32)[None]   # (B, W)
    pad = jnp.arange(W, dtype=jnp.int32)[None] >= n_real[:, None]   # (B, W)
    if block_tables is None:
        scrub_idx = jnp.where(pad, row_idx, jnp.int32(2**30))
        b_ix = jnp.arange(B, dtype=jnp.int32)[:, None]
        new_caches = jax.tree.map(
            lambda a: a.at[:, b_ix, scrub_idx].set(jnp.zeros((), a.dtype),
                                                   mode="drop"),
            new_caches)
    else:
        nb = block_tables.shape[1]
        blk = row_idx // page_size
        off = row_idx % page_size
        page = jnp.take_along_axis(block_tables, jnp.minimum(blk, nb - 1),
                                   axis=1)
        page = jnp.where(pad & (blk < nb) & (page != 0), page,
                         jnp.int32(2**30))
        new_caches = jax.tree.map(
            lambda a: a.at[:, page, off].set(jnp.zeros((), a.dtype),
                                             mode="drop"),
            new_caches)
    return targets, new_caches
