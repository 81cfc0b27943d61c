"""Jit'd public wrappers for the decode-attention kernels (dense + paged).

Quantized KV pools: every paged wrapper takes optional ``k_scale``/
``v_scale`` operands (per-(block, slot, kv-head) f32, shape
``(num_blocks, block_size, K)``).  Passing them flips the kernel into
dequantize-in-register mode — the int8/fp8 pool leaves are the only K/V
bytes streamed from HBM.  Presence of the operands is the switch, so one
jitted wrapper serves every ``kv_dtype`` without retracing on value.
"""
from __future__ import annotations

import functools

import jax

from .kernel import (decode_attention_fwd, paged_decode_attention_fwd,
                     ragged_paged_attention_fwd, suggest_block_size)
from .quant import (KV_DTYPES, dequantize_kv, is_quantized, quantize_kv,
                    resolve_kv_dtype)
from .ref import (decode_attention_ref, paged_decode_attention_quant_ref,
                  paged_decode_attention_ref,
                  ragged_paged_attention_quant_ref,
                  ragged_paged_attention_ref)


@functools.partial(jax.jit, static_argnames=("window", "softcap", "scale",
                                             "block_k", "interpret"))
def decode_attention(q, k_cache, v_cache, q_pos, cache_pos, *,
                     k_scale=None, v_scale=None,
                     window: int | None = None, softcap: float | None = None,
                     scale: float | None = None, block_k: int = 512,
                     interpret: bool = False):
    """One-token decode attention.  q: (B,H,D); caches (B,S,K,D);
    optional quantized-cache scales (B,S,K)."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    return decode_attention_fwd(q, k_cache, v_cache, q_pos, cache_pos,
                                scale=scale, softcap=softcap, window=window,
                                block_k=block_k, k_scale=k_scale,
                                v_scale=v_scale, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("window", "softcap", "scale",
                                             "interpret"))
def paged_decode_attention(q, k_pool, v_pool, block_tables, q_pos, *,
                           k_scale=None, v_scale=None,
                           window: int | None = None,
                           softcap: float | None = None,
                           scale: float | None = None,
                           interpret: bool = False):
    """One-token decode attention over a paged KV pool.

    q: (B,H,D); pools (num_blocks, block_size, K, D); block_tables (B,nb)
    int32 physical block ids (-1 = unused); q_pos (B,) absolute positions.
    The kernel streams each request's blocks straight out of the shared pool
    via scalar-prefetched table lookups (no densifying gather)."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    return paged_decode_attention_fwd(q, k_pool, v_pool, block_tables, q_pos,
                                      scale=scale, softcap=softcap,
                                      window=window, k_scale=k_scale,
                                      v_scale=v_scale, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("window", "softcap", "scale",
                                             "interpret"))
def ragged_paged_attention(q, k_pool, v_pool, block_tables, row_ids,
                           token_pos, *, k_scale=None, v_scale=None,
                           window: int | None = None,
                           softcap: float | None = None,
                           scale: float | None = None,
                           interpret: bool = False):
    """Mixed prefill-chunk + decode attention over a paged KV pool.

    q: (T,H,D) packed tokens; pools (num_blocks, block_size, K, D);
    block_tables (R,nb) int32 physical block ids (-1 = unused); row_ids (T,)
    request row of each packed token (-1 = pad lane); token_pos (T,) absolute
    positions (-1 = pad lane).  One dispatch serves prefill chunks and decode
    rows alike: every token walks its own request's blocks, from the
    scalar-prefetched table, in a loop bounded by what its mask can keep —
    up to the block holding its position (causal), from the block holding
    the window's oldest position, and not at all for a pad lane — copying
    several pages of all kv heads at once; intra-chunk causality,
    cross-request isolation, and pad-lane suppression are all the same
    mask.

    Multi-token VERIFY rows (speculative decoding) are the same packing: a
    row that feeds k tokens at consecutive tail positions [P, P+k) is
    indistinguishable from a k-token prefill chunk — K/V for all k positions
    is written before any token reads, and each token attends causally at
    its own position, which is exactly the draft-verification semantics the
    engine's acceptance rule needs.  k = 1 degenerates to today's
    single-token decode (``paged_decode_attention`` is literally this kernel
    with ``row_ids = arange(B)``).

    ``k_scale``/``v_scale`` (num_blocks, block_size, K) f32 mark the pool
    as quantized.  How many pages one copy covers is derived from the
    shapes (``kernel.pages_per_group``); there is nothing to tune."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    return ragged_paged_attention_fwd(q, k_pool, v_pool, block_tables,
                                      row_ids, token_pos, scale=scale,
                                      softcap=softcap, window=window,
                                      k_scale=k_scale, v_scale=v_scale,
                                      interpret=interpret)


__all__ = ["decode_attention", "decode_attention_ref",
           "paged_decode_attention", "paged_decode_attention_ref",
           "paged_decode_attention_quant_ref",
           "ragged_paged_attention", "ragged_paged_attention_ref",
           "ragged_paged_attention_quant_ref",
           "KV_DTYPES", "resolve_kv_dtype", "is_quantized",
           "quantize_kv", "dequantize_kv", "suggest_block_size"]
