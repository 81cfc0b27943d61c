"""Flash-decoding Pallas TPU kernel: one query token vs a long KV cache.

This is THE serving hot spot (decode_32k / long_500k shapes): arithmetic
intensity is O(1) FLOP/byte — every cached K/V byte is read once per step —
so the kernel is HBM-bandwidth-bound and the design goal is to stream K/V
through VMEM at full bandwidth while keeping the softmax state in registers.

TPU adaptation: instead of CUDA's one-warp-per-split + shared-memory
reduction, we put the cache-sequence axis LAST in the grid — TPU executes it
sequentially per (batch, kv-head), so the online-softmax state (m, l, acc)
lives in VMEM scratch carried across sequence blocks, and no cross-block
reduction pass is needed.  All G = H/K query heads of a kv head are
processed together as a (G, D) tile so the (G, bk) score matmul feeds the
MXU/VPU with aligned shapes.

Ring-buffer semantics come for free: the cache's per-slot absolute positions
are streamed alongside K/V and masking is positional, so the same kernel
serves full caches, sliding-window rings, and partially-filled prefixes.

Grid: (B, K, num_kv_blocks); blocks: q (G,D), k/v (bk,D), pos (bk,).

Paged variant (``paged_decode_attention_fwd``): K/V live in a global block
pool (num_blocks, block_size, K, D) shared by every request; each request
brings a block table (its logical→physical block mapping).  The table and the
query positions are scalar-prefetch operands, so the BlockSpec index map
resolves ``table[b, j]`` BEFORE the kernel body runs and the DMA engine
streams exactly the blocks the request owns — no host gather, no densified
copy of the cache.  Slot positions are implicit (logical block j covers
absolute positions [j·bs, (j+1)·bs)), so causal masking doubles as validity
masking: padded table entries (clamped to block 0) always sit beyond the
query position.

Ragged variant (``ragged_paged_attention_fwd``): the serving engine's unified
token-budget tick packs prefill CHUNKS and decode rows into one fixed-shape
token batch, so the query axis is tokens, not requests — several consecutive
tokens may belong to one request while their neighbors belong to others.  A
third scalar-prefetch operand ``row_ids`` maps packed token t to its
request's row in the block table, so the index map gathers
``table[row_ids[t], j]`` per TOKEN and each token streams exactly its own
request's blocks.  Causality is per token (``kpos <= token_pos[t]``), which
is simultaneously the causal intra-chunk mask (a chunk token sees earlier
chunk tokens, written in this same dispatch), the cross-request isolation
(different requests own disjoint physical blocks), and the pad-lane kill
(pad tokens carry ``token_pos = -1`` so every position is masked and the
zero-l guard emits exact zeros).  Single-token paged decode is the special
case ``row_ids == arange(B)`` and is implemented that way.  Speculative
VERIFY rows (k fed tokens at consecutive tail positions of one request) are
the same packing as a k-token prefill chunk — no kernel changes needed for
speculative decoding; the engine's acceptance rule consumes the per-position
logits downstream.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(qpos_ref, q_ref, k_ref, v_ref, pos_ref, *rest, scale: float,
            softcap: float | None, window: int | None, num_kv_blocks: int,
            quantized: bool):
    # With a quantized cache two per-slot-per-head f32 scale operands ride
    # after K/V; dequant is an in-register (bk, 1) × (bk, D) broadcast.
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[...].astype(jnp.float32)                        # (G, D)
    k = k_ref[...].astype(jnp.float32)                        # (bk, D)
    if quantized:
        k = k * ks_ref[...].astype(jnp.float32)               # (bk,1) bcast
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    kpos = pos_ref[...]                                       # (1, bk) int32
    qp = qpos_ref[0]
    mask = (kpos >= 0) & (kpos <= qp)
    if window is not None:
        mask &= (qp - kpos) < window
    s = jnp.where(mask, s, NEG_INF)                           # (G, bk) via bcast

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
    v = v_ref[...].astype(jnp.float32)
    if quantized:
        v = v * vs_ref[...].astype(jnp.float32)
    pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_scr[...] = acc_scr[...] * alpha + pv
    m_scr[...] = m_new

    @pl.when(ik == num_kv_blocks - 1)
    def _finalize():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_scr[...] / l).astype(o_ref.dtype)


def decode_attention_fwd(q, k_cache, v_cache, q_pos, cache_pos, *,
                         scale: float, softcap: float | None,
                         window: int | None, block_k: int = 512,
                         k_scale=None, v_scale=None,
                         interpret: bool = False):
    """q: (B,H,D); caches (B,S,K,D); cache_pos (B,S); q_pos (B,).

    ``k_scale``/``v_scale`` (B,S,K) f32, when given, mark the caches as
    quantized (int8/fp8) and are applied in-register after the stream."""
    B, H, D = q.shape
    S, K = k_cache.shape[1], k_cache.shape[2]
    G = H // K
    quantized = k_scale is not None
    block_k = min(block_k, S)
    pad = (-S) % block_k
    if pad:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
        cache_pos = jnp.pad(cache_pos, ((0, 0), (0, pad)), constant_values=-1)
        if quantized:
            k_scale = jnp.pad(k_scale, ((0, 0), (0, pad), (0, 0)))
            v_scale = jnp.pad(v_scale, ((0, 0), (0, pad), (0, 0)))
    Sp = k_cache.shape[1]
    nk = Sp // block_k

    qh = q.reshape(B, K, G, D)
    kt = k_cache.transpose(0, 2, 1, 3)                        # (B,K,S,D)
    vt = v_cache.transpose(0, 2, 1, 3)
    pos2 = cache_pos[:, None, :]                              # (B,1,S)

    grid = (B, K, nk)
    kern = functools.partial(_kernel, scale=scale, softcap=softcap,
                             window=window, num_kv_blocks=nk,
                             quantized=quantized)
    in_specs = [
        pl.BlockSpec((1,), lambda b, h, ik: (b,)),                      # q_pos
        pl.BlockSpec((None, None, G, D), lambda b, h, ik: (b, h, 0, 0)),
        pl.BlockSpec((None, None, block_k, D), lambda b, h, ik: (b, h, ik, 0)),
        pl.BlockSpec((None, None, block_k, D), lambda b, h, ik: (b, h, ik, 0)),
        pl.BlockSpec((None, 1, block_k), lambda b, h, ik: (b, 0, ik)),  # pos
    ]
    operands = [q_pos, qh, kt, vt, pos2]
    if quantized:
        # (B,S,K) → (B,K,S,1): a (block_k, 1) tile broadcasting over D.
        in_specs += [
            pl.BlockSpec((None, None, block_k, 1),
                         lambda b, h, ik: (b, h, ik, 0)),
            pl.BlockSpec((None, None, block_k, 1),
                         lambda b, h, ik: (b, h, ik, 0)),
        ]
        operands += [k_scale.transpose(0, 2, 1)[..., None],
                     v_scale.transpose(0, 2, 1)[..., None]]
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, None, G, D), lambda b, h, ik: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, K, G, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
        interpret=interpret,
    )(*operands)
    return out.reshape(B, H, D)


def _ragged_kernel(rows_ref, bt_ref, qpos_ref, nblk_ref, q_ref, k_ref, v_ref,
                   *rest, scale: float, softcap: float | None,
                   window: int | None, block_size: int,
                   num_logical_blocks: int, quantized: bool):
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    t = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Per-token early-out: blocks past the row's live count are -1 table
    # entries — fully masked below, so their update is an exact identity
    # (p = 0, alpha = 1) and skipping the whole body is lossless.  Their
    # index map clamps to block 0 too, so the revolving input buffer sees
    # the same block every tail step and the DMA is elided: short rows in
    # a batch with one long row stop paying the long row's gather + QK.
    live = nblk_ref[rows_ref[t]]

    @pl.when(j < live)
    def _accumulate():
        q = q_ref[...].astype(jnp.float32)                    # (G, D)
        k = k_ref[...].astype(jnp.float32)                    # (bs, D)
        if quantized:
            k = k * ks_ref[...].astype(jnp.float32)           # (bs,1) bcast
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        # logical block j covers absolute positions [j*bs, (j+1)*bs): masking
        # is positional, so clamped pad blocks (positions beyond qp) vanish
        # here, as do pad tokens entirely (qp = -1 masks everything, and
        # live = 0 already skips them; l stays 0).
        kpos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_size), 1)                    # (1, bs)
        qp = qpos_ref[t]
        mask = kpos <= qp
        if window is not None:
            mask &= (qp - kpos) < window
        s = jnp.where(mask, s, NEG_INF)                       # (G, bs) bcast

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # explicit re-mask: when EVERY position is masked (window start of a
        # live block), s - m_new is NEG_INF - NEG_INF = 0 and exp would emit
        # 1s; zeroing by mask keeps l exact so finalize can guard on l == 0.
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[...].astype(jnp.float32)
        if quantized:
            v = v * vs_ref[...].astype(jnp.float32)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = m_new

    @pl.when(j == num_logical_blocks - 1)
    def _finalize():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_scr[...] / l).astype(o_ref.dtype)


# nb must stay "arbitrary": the online-softmax scratch (m, l, acc) is
# carried across the block axis, so those iterations are sequential by
# construction.  T and K carry no cross-iteration state and default to
# "parallel" so Mosaic may split them across megacore.
DEFAULT_DIMENSION_SEMANTICS = ("parallel", "parallel", "arbitrary")


def suggest_block_size(head_dim: int, group_size: int, *,
                       vmem_budget_bytes: int = 32 * 2 ** 20,
                       kv_itemsize: int = 4,
                       candidates: tuple = (512, 256, 128, 64, 32, 16, 8)
                       ) -> int:
    """Largest candidate block_size whose per-iteration VMEM working set
    (double-buffered K/V tiles + scale columns + q tile + softmax scratch,
    all f32 in-register) fits ``vmem_budget_bytes``.

    A tuning hook, not an oracle: real-TPU block_size also trades gather
    granularity against pool fragmentation, so callers treat this as the
    upper bound and benchmark downward."""
    for bs in candidates:
        kv_tiles = 2 * 2 * bs * head_dim * kv_itemsize      # K+V, 2x buffered
        scale_cols = 2 * 2 * bs * 4                         # k/v scale tiles
        q_tile = group_size * head_dim * 4
        scratch = group_size * (head_dim + 2) * 4           # m, l, acc
        if kv_tiles + scale_cols + q_tile + scratch <= vmem_budget_bytes:
            return bs
    return candidates[-1]


def ragged_paged_attention_fwd(q, k_pool, v_pool, block_tables, row_ids,
                               token_pos, *, scale: float,
                               softcap: float | None, window: int | None,
                               k_scale=None, v_scale=None,
                               dimension_semantics: tuple | None = None,
                               interpret: bool = False):
    """q: (T,H,D) packed tokens; pools (N,bs,K,D); block_tables (R,nb) int32,
    -1 = unused; row_ids (T,) request row of each token (-1 = pad lane);
    token_pos (T,) absolute position of each token (-1 = pad lane).

    Grid (T, K, nb): the per-token row gather happens in the BlockSpec index
    map — ``bt[rows[t], j]`` — so the DMA engine streams, for every packed
    token, exactly the blocks of the request that token belongs to.  Pad
    lanes (row -1 / pos -1) clamp to request row 0 / the null block and are
    fully masked, producing exact zeros.

    ``k_scale``/``v_scale`` (N,bs,K) f32, when given, mark the pool as
    quantized (int8/fp8 leaves): the kernel dequantizes in-register after
    the block-table gather, so HBM only ever streams the narrow bytes.

    A fourth scalar-prefetch operand carries per-row live-block counts
    (``sum(block_tables >= 0, axis=1)`` — tables are dense prefixes), and
    the kernel skips the zero-contribution tail of the nb axis per token."""
    T, H, D = q.shape
    N, bs, K, _ = k_pool.shape
    G = H // K
    nb = block_tables.shape[1]
    quantized = k_scale is not None
    # -1 pads clamp to block 0 (the engine's reserved null block); their
    # implicit positions j*bs+p exceed token_pos, so the causal mask kills
    # them.  Pad ROWS clamp to row 0; token_pos = -1 masks every position.
    bt = jnp.maximum(block_tables, 0).astype(jnp.int32)
    rows = jnp.clip(row_ids, 0, block_tables.shape[0] - 1).astype(jnp.int32)
    # Per-row live-block counts for the early-out (tables are dense
    # prefixes: valid entries precede every -1).
    nblk = jnp.sum(block_tables >= 0, axis=1).astype(jnp.int32)

    qh = q.reshape(T, K, G, D)
    kt = k_pool.transpose(0, 2, 1, 3)                         # (N,K,bs,D)
    vt = v_pool.transpose(0, 2, 1, 3)

    kern = functools.partial(_ragged_kernel, scale=scale, softcap=softcap,
                             window=window, block_size=bs,
                             num_logical_blocks=nb, quantized=quantized)
    in_specs = [
        pl.BlockSpec((None, None, G, D),
                     lambda t, h, j, rows, bt, qp, nblk: (t, h, 0, 0)),  # q
        pl.BlockSpec((None, None, bs, D),
                     lambda t, h, j, rows, bt, qp, nblk:
                     (bt[rows[t], j], h, 0, 0)),                         # k
        pl.BlockSpec((None, None, bs, D),
                     lambda t, h, j, rows, bt, qp, nblk:
                     (bt[rows[t], j], h, 0, 0)),                         # v
    ]
    operands = [qh, kt, vt]
    if quantized:
        # (N,bs,K) → (N,K,bs,1): a (bs, 1) tile gathered by the same block
        # index, broadcasting over D in the kernel.
        in_specs += [
            pl.BlockSpec((None, None, bs, 1),
                         lambda t, h, j, rows, bt, qp, nblk:
                         (bt[rows[t], j], h, 0, 0)),
            pl.BlockSpec((None, None, bs, 1),
                         lambda t, h, j, rows, bt, qp, nblk:
                         (bt[rows[t], j], h, 0, 0)),
        ]
        operands += [k_scale.transpose(0, 2, 1)[..., None],
                     v_scale.transpose(0, 2, 1)[..., None]]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,                        # rows, bt, qp, nblk
        grid=(T, K, nb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, None, G, D),
                               lambda t, h, j, rows, bt, qp, nblk:
                               (t, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
    )
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=(dimension_semantics
                                 or DEFAULT_DIMENSION_SEMANTICS))
    out = pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, K, G, D), q.dtype),
        interpret=interpret, **kwargs,
    )(rows, bt, token_pos.astype(jnp.int32), nblk, *operands)
    return out.reshape(T, H, D)


def paged_decode_attention_fwd(q, k_pool, v_pool, block_tables, q_pos, *,
                               scale: float, softcap: float | None,
                               window: int | None, k_scale=None,
                               v_scale=None, interpret: bool = False):
    """q: (B,H,D); pools (N,bs,K,D); block_tables (B,nb) int32, -1 = unused;
    q_pos (B,) absolute position of the query token.

    Single-token decode is the ragged kernel's degenerate packing: one token
    per request, ``row_ids == arange(B)``."""
    B = q.shape[0]
    return ragged_paged_attention_fwd(
        q, k_pool, v_pool, block_tables, jnp.arange(B, dtype=jnp.int32),
        q_pos, scale=scale, softcap=softcap, window=window,
        k_scale=k_scale, v_scale=v_scale, interpret=interpret)
