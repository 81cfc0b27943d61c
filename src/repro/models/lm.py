"""Unified decoder LM over the segment/pattern layout.

One code path serves all 10 assigned architectures: the stack is a tuple of
segments, each segment scans over `repeat` stacked copies of its layer
pattern (see config.layout()).  Shared-attention blocks (zamba2) keep their
parameters OUTSIDE the scan (closure constants) while their KV caches are
scanned — one cache per application.

Public API:
  init_params(key, cfg)                        -> params
  param_axes(cfg)                              -> logical sharding axes (same tree)
  forward(params, inputs, positions, cfg)      -> (logits, aux)       [train/score]
  init_decode_caches(cfg, batch, max_len)      -> caches
  prefill(params, inputs, positions, cfg, max_len) -> (last_logits, caches)
  decode_step(params, caches, inputs, positions, cfg) -> (logits, caches)

`inputs` is token ids (B,S) int32 for input_mode="tokens", or precomputed
frontend embeddings (B,S,d) for "embeds" (audio/vlm stubs).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from . import attention as attn_mod
from . import mamba2 as mamba_mod
from .config import LayerSpec, ModelConfig, Segment
from .layers import (dtype_of, embed_axes, embed_init, embed_lookup, rmsnorm,
                     rmsnorm_axes, rmsnorm_init, softcap, stack_init, unembed)
from .mlp import mlp, mlp_axes, mlp_init
from .moe import moe, moe_axes, moe_init


# ====================================================================== init
def _block_init(key, cfg: ModelConfig, spec: LayerSpec) -> dict:
    dt = dtype_of(cfg)
    d = cfg.d_model
    if spec.kind == "mamba":
        k1, k2 = jax.random.split(key)
        return {"norm": rmsnorm_init(d, dt), "mamba": mamba_mod.mamba_init(k1, cfg)}
    if spec.kind == "shared_attn":
        return {}  # parameters live in params["shared_attn"], not per layer
    k1, k2 = jax.random.split(key)
    p = {
        "norm_attn": rmsnorm_init(d, dt),
        "attn": attn_mod.attn_init(k1, cfg),
        "norm_mlp": rmsnorm_init(d, dt),
    }
    if cfg.post_norm:
        p["post_norm_attn"] = rmsnorm_init(d, dt)
        p["post_norm_mlp"] = rmsnorm_init(d, dt)
    if spec.kind == "attn_moe":
        p["moe"] = moe_init(k2, cfg)
    else:
        p["mlp"] = mlp_init(k2, cfg)
    return p


def _block_axes(cfg: ModelConfig, spec: LayerSpec) -> dict:
    if spec.kind == "mamba":
        return {"norm": rmsnorm_axes(), "mamba": mamba_mod.mamba_axes(cfg)}
    if spec.kind == "shared_attn":
        return {}
    p = {
        "norm_attn": rmsnorm_axes(),
        "attn": attn_mod.attn_axes(cfg),
        "norm_mlp": rmsnorm_axes(),
    }
    if cfg.post_norm:
        p["post_norm_attn"] = rmsnorm_axes()
        p["post_norm_mlp"] = rmsnorm_axes()
    if spec.kind == "attn_moe":
        p["moe"] = moe_axes(cfg)
    else:
        p["mlp"] = mlp_axes()
    return p


def _shared_attn_init(key, cfg: ModelConfig) -> dict:
    dt = dtype_of(cfg)
    d = cfg.d_model
    k1, k2 = jax.random.split(key)
    return {
        "norm_attn": rmsnorm_init(2 * d, dt),
        "attn": attn_mod.attn_init(k1, cfg, d_in=2 * d, d_out=d),
        "norm_mlp": rmsnorm_init(2 * d, dt),
        "mlp": mlp_init(k2, cfg, d_in=2 * d, d_out=d),
    }


def _shared_attn_axes(cfg: ModelConfig) -> dict:
    return {
        "norm_attn": rmsnorm_axes(),
        "attn": attn_mod.attn_axes(cfg),
        "norm_mlp": rmsnorm_axes(),
        "mlp": mlp_axes(),
    }


def init_params(key, cfg: ModelConfig) -> dict:
    keys = jax.random.split(key, len(cfg.layout()) + 3)
    params: dict[str, Any] = {
        "embed": embed_init(keys[0], cfg.vocab_size, cfg.d_model, dtype_of(cfg)),
        "final_norm": rmsnorm_init(cfg.d_model, dtype_of(cfg)),
    }
    if not cfg.tie_embeddings:
        params["head"] = embed_init(keys[1], cfg.vocab_size, cfg.d_model, dtype_of(cfg))
    segments = []
    for si, seg in enumerate(cfg.layout()):
        seg_keys = jax.random.split(keys[2 + si], len(seg.pattern))
        pos_params = []
        for pi, spec in enumerate(seg.pattern):
            init_one = functools.partial(_block_init, cfg=cfg, spec=spec)
            pos_params.append(stack_init(init_one, seg_keys[pi], seg.repeat))
        segments.append(tuple(pos_params))
    params["segments"] = tuple(segments)
    if any(s.kind == "shared_attn" for seg in cfg.layout() for s in seg.pattern):
        params["shared_attn"] = _shared_attn_init(keys[-1], cfg)
    return params


def param_axes(cfg: ModelConfig) -> dict:
    """Logical axes tree mirroring init_params; scan adds a leading 'layers'
    axis to every per-segment leaf."""
    def add_layer_axis(tree):
        return jax.tree.map(lambda a: ("layers",) + tuple(a), tree,
                            is_leaf=lambda x: isinstance(x, tuple) and
                            all(isinstance(e, (str, type(None))) for e in x))

    axes: dict[str, Any] = {
        "embed": embed_axes(),
        "final_norm": rmsnorm_axes(),
    }
    if not cfg.tie_embeddings:
        axes["head"] = embed_axes()
    segments = []
    for seg in cfg.layout():
        pos_axes = []
        for spec in seg.pattern:
            pos_axes.append(add_layer_axis(_block_axes(cfg, spec)))
        segments.append(tuple(pos_axes))
    axes["segments"] = tuple(segments)
    if any(s.kind == "shared_attn" for seg in cfg.layout() for s in seg.pattern):
        axes["shared_attn"] = _shared_attn_axes(cfg)
    return axes


# ==================================================================== blocks
def _barrier(y, cfg: ModelConfig):
    """Keep the TP all-reduce on this (bf16) tensor instead of letting XLA
    fuse the downstream f32 norm-upcast into it (see config.comm_bf16_barrier)."""
    if cfg.comm_bf16_barrier:
        return jax.lax.optimization_barrier(y)
    return y


def _apply_block(block_params, x, positions, *, cfg: ModelConfig,
                 spec: LayerSpec, cache, shared_params, embeds0, mode: str,
                 block_table=None, row_ids=None):
    """One layer. Returns (x, new_cache, aux).

    With ``block_table`` set, ``cache`` is the layer's slice of the paged KV
    pool and attention goes through the paged path (suffix prefill, paged
    decode, or — with ``row_ids`` — the packed ragged mixed step); only
    pure-attention layer kinds support it (see supports_paged).
    """
    aux = jnp.zeros((), jnp.float32)
    if block_table is not None and spec.kind not in ("attn_mlp", "attn_moe"):
        raise NotImplementedError(
            f"paged KV cache does not support layer kind {spec.kind!r}")
    if spec.kind == "mamba":
        h = rmsnorm(block_params["norm"], x)
        y, new_cache = mamba_mod.mamba_block(block_params["mamba"], h, cfg=cfg,
                                             cache=cache)
        return x + _barrier(y, cfg), new_cache, aux

    if spec.kind == "shared_attn":
        p = shared_params
        u = jnp.concatenate([x, embeds0], axis=-1)
        h = rmsnorm(p["norm_attn"], u)
        if mode == "prefill":
            y, new_cache = attn_mod.prefill_cache(
                p["attn"], h, positions, cfg=cfg, spec=spec,
                max_len=cache["pos"].shape[1])
        else:
            y, new_cache = attn_mod.attention(p["attn"], h, positions, cfg=cfg,
                                              spec=spec, cache=cache)
        x = x + _barrier(y, cfg)
        v = jnp.concatenate([x, embeds0], axis=-1)
        x = x + _barrier(mlp(p["mlp"], rmsnorm(p["norm_mlp"], v)), cfg)
        return x, new_cache, aux

    # attn_mlp / attn_moe; the scopes name the paged tick's phases in its
    # ops' metadata (the paged path names qkv, kv_pool and attention inside)
    with jax.named_scope("qkv"):
        h = rmsnorm(block_params["norm_attn"], x)
    if block_table is not None:
        y, new_cache = attn_mod.paged_attention(
            block_params["attn"], h, positions, cfg=cfg, spec=spec,
            pool=cache, block_table=block_table, row_ids=row_ids)
    elif mode == "prefill":
        y, new_cache = attn_mod.prefill_cache(
            block_params["attn"], h, positions, cfg=cfg, spec=spec,
            max_len=cache["pos"].shape[1])
    else:
        y, new_cache = attn_mod.attention(block_params["attn"], h, positions,
                                          cfg=cfg, spec=spec, cache=cache)
    with jax.named_scope("attention"):
        if cfg.post_norm:
            y = rmsnorm(block_params["post_norm_attn"], y)
        x = x + _barrier(y, cfg)
    with jax.named_scope("mlp"):
        h = rmsnorm(block_params["norm_mlp"], x)
        if spec.kind == "attn_moe":
            y, aux = moe(block_params["moe"], h, cfg=cfg)
        else:
            y = mlp(block_params["mlp"], h)
        if cfg.post_norm:
            y = rmsnorm(block_params["post_norm_mlp"], y)
        return x + _barrier(y, cfg), new_cache, aux


def _run_segment(seg_params, x, positions, *, cfg: ModelConfig, seg: Segment,
                 caches, shared_params, embeds0, mode: str, block_table=None,
                 row_ids=None):
    """Scan over the segment's `repeat` axis.

    caches: tuple per pattern position of stacked (R,...) cache trees, or
    None (train/score).  block_table (paged serving) is one (B,nb) mapping
    shared by every layer — each layer owns its own pool slice but the
    logical→physical block mapping is per-request, not per-layer.  row_ids
    (packed ragged step) maps each token of the single packed row to its
    request's block-table row; it too is layer-invariant.
    Returns (x, aux_sum, new_caches|None).
    """
    with_cache = caches is not None

    def body(carry, xs):
        x, aux = carry
        layer_params, layer_caches = xs
        new_caches = []
        for i, spec in enumerate(seg.pattern):
            c_i = layer_caches[i] if with_cache else None
            x, nc, aux_i = _apply_block(layer_params[i], x, positions, cfg=cfg,
                                        spec=spec, cache=c_i,
                                        shared_params=shared_params,
                                        embeds0=embeds0, mode=mode,
                                        block_table=block_table,
                                        row_ids=row_ids)
            aux = aux + aux_i
            new_caches.append(nc if with_cache else jnp.zeros((), jnp.int8))
        return (x, aux), tuple(new_caches)

    if cfg.remat and mode == "train":
        body = jax.checkpoint(body)

    if with_cache:
        xs = (seg_params, caches)
    else:
        dummy = jnp.zeros((seg.repeat,), jnp.int8)
        xs = (seg_params, tuple(dummy for _ in seg.pattern))
    (x, aux), new_caches = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32)), xs,
        unroll=seg.repeat if cfg.scan_unroll else 1)
    return x, aux, (new_caches if with_cache else None)


# =================================================================== forward
def _embed_inputs(params, inputs, cfg: ModelConfig):
    if cfg.input_mode == "embeds":
        return inputs.astype(dtype_of(cfg))
    return embed_lookup(params["embed"], inputs, scale=cfg.embed_scale,
                        d=cfg.d_model)


def _head(params, x, cfg: ModelConfig):
    x = rmsnorm(params["final_norm"], x)
    table = params["embed"] if cfg.tie_embeddings else params["head"]
    logits = unembed(table, x)
    return softcap(logits, cfg.final_logit_softcap)


def forward(params, inputs, positions, cfg: ModelConfig, *, mode: str = "train"):
    """Full-sequence forward (no caches). Returns (logits, aux)."""
    x = _embed_inputs(params, inputs, cfg)
    embeds0 = x
    aux = jnp.zeros((), jnp.float32)
    for seg, seg_params in zip(cfg.layout(), params["segments"]):
        x, aux_s, _ = _run_segment(seg_params, x, positions, cfg=cfg, seg=seg,
                                   caches=None,
                                   shared_params=params.get("shared_attn"),
                                   embeds0=embeds0, mode=mode)
        aux = aux + aux_s
    return _head(params, x, cfg), aux


# ===================================================================== cache
def _block_cache_init(cfg: ModelConfig, spec: LayerSpec, batch: int, max_len: int):
    if spec.kind == "mamba":
        return mamba_mod.mamba_cache_init(cfg, batch)
    return attn_mod.init_cache(cfg, spec, batch, max_len)


def init_decode_caches(cfg: ModelConfig, batch: int, max_len: int):
    """Tuple per segment of tuple per pattern position of stacked caches."""
    caches = []
    for seg in cfg.layout():
        pos_caches = []
        for spec in seg.pattern:
            one = _block_cache_init(cfg, spec, batch, max_len)
            stacked = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (seg.repeat,) + a.shape), one)
            pos_caches.append(stacked)
        caches.append(tuple(pos_caches))
    return tuple(caches)


def cache_axes(cfg: ModelConfig):
    """Logical axes for the cache tree (leading 'layers' from stacking)."""
    def add_layer(tree):
        return jax.tree.map(lambda a: ("layers",) + tuple(a), tree,
                            is_leaf=lambda x: isinstance(x, tuple) and
                            all(isinstance(e, (str, type(None))) for e in x))

    out = []
    for seg in cfg.layout():
        pos = []
        for spec in seg.pattern:
            if spec.kind == "mamba":
                pos.append(add_layer(mamba_mod.mamba_cache_axes()))
            else:
                pos.append(add_layer(attn_mod.cache_axes()))
        out.append(tuple(pos))
    return tuple(out)


def prefill(params, inputs, positions, cfg: ModelConfig, *, max_len: int):
    """Run the prompt, build caches.  Returns (last-token logits, caches)."""
    x = _embed_inputs(params, inputs, cfg)
    embeds0 = x
    caches = init_decode_caches(cfg, x.shape[0], max_len)
    new_caches = []
    for seg, seg_params, seg_caches in zip(cfg.layout(), params["segments"], caches):
        x, _, nc = _run_segment(seg_params, x, positions, cfg=cfg, seg=seg,
                                caches=seg_caches,
                                shared_params=params.get("shared_attn"),
                                embeds0=embeds0, mode="prefill")
        new_caches.append(nc)
    logits = _head(params, x[:, -1:, :], cfg)
    return logits[:, 0, :], tuple(new_caches)


# ===================================================================== paged
def supports_paged(cfg: ModelConfig) -> bool:
    """Paged KV serving needs token inputs (the prefix trie is keyed by
    token blocks) and pure-attention layers (SSM/conv state is O(1) per
    request and carries the whole history — it cannot be block-shared)."""
    return cfg.input_mode == "tokens" and all(
        s.kind in ("attn_mlp", "attn_moe")
        for seg in cfg.layout() for s in seg.pattern)


def supports_speculative(cfg: ModelConfig) -> bool:
    """Speculative (multi-token verify) decode rows need the paged path:
    a k-token row rides the packed ragged dispatch as k+1 fed tokens, and
    rejected-draft K/V is undone by truncating the row's block table
    (serving/kvcache.rollback_writes).  The dense slot cache has no write
    watermark to rewind, and SSM/conv state folds the whole history into
    O(1) per request — it cannot drop the last j tokens at all.  Mirrors
    ``supports_paged``; engines gate on this exactly as they gate paging."""
    return supports_paged(cfg)


def init_paged_pools(cfg: ModelConfig, num_blocks: int, block_size: int,
                     kv_dtype: str | None = None):
    """Global KV block pool, same tree layout as init_decode_caches but with
    (num_blocks, block_size) replacing the (batch, seq) plane.

    ``kv_dtype`` (default ``cfg.kv_dtype``) selects the storage precision;
    quantized pools carry per-(block, slot, kv-head) scale leaves that ride
    the same tree through donation, spill/adopt, and sharding."""
    kv_dtype = cfg.kv_dtype if kv_dtype is None else kv_dtype
    pools = []
    for seg in cfg.layout():
        pos_pools = []
        for spec in seg.pattern:
            one = attn_mod.init_paged_pool(cfg, num_blocks, block_size,
                                           kv_dtype=kv_dtype)
            stacked = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (seg.repeat,) + a.shape), one)
            pos_pools.append(stacked)
        pools.append(tuple(pos_pools))
    return tuple(pools)


def paged_pool_axes(cfg: ModelConfig, kv_dtype: str | None = None):
    """Logical axes tree mirroring ``init_paged_pools`` (the leading
    'layers' axis comes from stacking, exactly as in ``cache_axes``)."""
    def add_layer(tree):
        return jax.tree.map(lambda a: ("layers",) + tuple(a), tree,
                            is_leaf=lambda x: isinstance(x, tuple) and
                            all(isinstance(e, (str, type(None))) for e in x))

    out = []
    for seg in cfg.layout():
        pos = []
        for spec in seg.pattern:
            pos.append(add_layer(attn_mod.paged_pool_axes(cfg,
                                                          kv_dtype=kv_dtype)))
        out.append(tuple(pos))
    return tuple(out)


def paged_prefill(params, pools, block_tables, inputs, positions,
                  cfg: ModelConfig):
    """Prefill a (possibly block-aligned-truncated) prompt suffix against the
    paged pool.  inputs (B,T) are the suffix tokens, positions (B,T) their
    absolute positions (row b starts at its reused prefix length L_b); the
    suffix attends to the reused prefix KV through the block table without
    recomputing it.  Returns (last-token logits, new pools)."""
    x = _embed_inputs(params, inputs, cfg)
    embeds0 = x
    new_pools = []
    for seg, seg_params, seg_pools in zip(cfg.layout(), params["segments"],
                                          pools):
        x, _, np_ = _run_segment(seg_params, x, positions, cfg=cfg, seg=seg,
                                 caches=seg_pools,
                                 shared_params=params.get("shared_attn"),
                                 embeds0=embeds0, mode="prefill",
                                 block_table=block_tables)
        new_pools.append(np_)
    logits = _head(params, x[:, -1:, :], cfg)
    return logits[:, 0, :], tuple(new_pools)


def paged_mixed_step(params, pools, block_tables, tokens, positions, row_ids,
                     sample_idx, cfg: ModelConfig):
    """ONE fixed-shape step over a packed ragged token batch: prefill chunks
    and decode rows share the dispatch (the serving engine's unified
    token-budget tick).

    tokens (T,) int32 packed tokens; positions (T,) absolute positions (-1 =
    pad lane); row_ids (T,) block-table row per token (-1 = pad);
    block_tables (R, nb); sample_idx the packed index each request row
    samples from — its decode token, or the final token of the prefill chunk
    that completed its prompt (rows with no boundary this tick point anywhere
    and their logits are ignored host-side).  With sample_idx (R,) one
    boundary token is gathered per row and logits are (R, V).  Speculative
    decoding passes sample_idx (R, J): row r's J fed tokens (its last
    committed token plus J-1 verified draft tokens, lanes repeated for rows
    with fewer), and logits are (R, J, V) — the target distributions the
    acceptance rule (models.sampling.speculative_verify) consumes, all from
    this same single dispatch.

    Every layer writes ALL packed K/V before attending, so a chunk token
    sees its same-dispatch predecessors AND any same-tick sibling's shared
    prefix blocks; the head runs only on the gathered boundary tokens, not
    the full packed row.  Returns (logits, new pools).

    Its phases are named scopes in the ops' metadata: ``embed``, per layer
    ``qkv``, ``kv_pool/write``, ``kv_pool/layout``, ``attention`` and
    ``mlp``, then ``head``."""
    with jax.named_scope("embed"):
        x = _embed_inputs(params, tokens[None], cfg)          # (1, T, d)
    embeds0 = x
    new_pools = []
    for seg, seg_params, seg_pools in zip(cfg.layout(), params["segments"],
                                          pools):
        x, _, np_ = _run_segment(seg_params, x, positions[None], cfg=cfg,
                                 seg=seg, caches=seg_pools,
                                 shared_params=params.get("shared_attn"),
                                 embeds0=embeds0, mode="mixed",
                                 block_table=block_tables, row_ids=row_ids)
        new_pools.append(np_)
    with jax.named_scope("head"):
        xb = jnp.take(x[0], sample_idx, axis=0)      # (R, d) or (R, J, d)
        if sample_idx.ndim == 1:
            return _head(params, xb[None], cfg)[0], tuple(new_pools)
        return _head(params, xb, cfg), tuple(new_pools)


def paged_decode_step(params, pools, block_tables, inputs, positions,
                      cfg: ModelConfig):
    """One decode step over the paged pool. inputs: (B,) tokens;
    positions (B,1).  Returns (logits (B,V), new pools)."""
    if inputs.ndim == 1:
        inputs = inputs[:, None]
    x = _embed_inputs(params, inputs, cfg)
    embeds0 = x
    new_pools = []
    for seg, seg_params, seg_pools in zip(cfg.layout(), params["segments"],
                                          pools):
        x, _, np_ = _run_segment(seg_params, x, positions, cfg=cfg, seg=seg,
                                 caches=seg_pools,
                                 shared_params=params.get("shared_attn"),
                                 embeds0=embeds0, mode="decode",
                                 block_table=block_tables)
        new_pools.append(np_)
    logits = _head(params, x, cfg)
    return logits[:, 0, :], tuple(new_pools)


def decode_step(params, caches, inputs, positions, cfg: ModelConfig):
    """One decode step. inputs: (B,) tokens or (B,1,d) embeds; positions (B,1).
    Returns (logits (B,V), new caches)."""
    if cfg.input_mode == "tokens" and inputs.ndim == 1:
        inputs = inputs[:, None]
    x = _embed_inputs(params, inputs, cfg)
    embeds0 = x
    new_caches = []
    for seg, seg_params, seg_caches in zip(cfg.layout(), params["segments"], caches):
        x, _, nc = _run_segment(seg_params, x, positions, cfg=cfg, seg=seg,
                                caches=seg_caches,
                                shared_params=params.get("shared_attn"),
                                embeds0=embeds0, mode="decode")
        new_caches.append(nc)
    logits = _head(params, x, cfg)
    return logits[:, 0, :], tuple(new_caches)
