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
brings a block table (its logical→physical block mapping).  Slot positions
are implicit (logical block j covers absolute positions [j·bs, (j+1)·bs)),
so causal masking doubles as validity masking.  Single-token paged decode
is the ragged variant below with one token per request
(``row_ids == arange(B)``) and is implemented that way.

Ragged variant (``ragged_paged_attention_fwd``): the serving engine's unified
token-budget tick packs prefill CHUNKS and decode rows into one fixed-shape
token batch, so the query axis is tokens, not requests — several consecutive
tokens may belong to one request while their neighbors belong to others.
The grid is one step per packed token (lane), covering all of its query
heads.  Scalar-prefetch operands carry each lane's request row
(``row_ids``), the block tables, each lane's position and each row's live
block count, so a lane knows before it copies anything which logical blocks
its mask can keep: up to the block holding its position (causal; a chunk's
early tokens stop before the blocks its later tokens wrote), from the block
holding the oldest position a window lets through, and none at all for a
pad lane (``token_pos = -1``), which writes exact zeros.  The lane walks
those blocks in an in-kernel loop over groups of pages: each page is one
(K, bs, D) slab of the pool, copied from HBM into a double-buffered VMEM
scratch, and group i+1's copies start before group i is computed.  The
online-softmax state (m, l, acc) of every query head rides the loop in
f32; masking stays per token and positional (``kpos <= token_pos[t]``), which
is at once the intra-chunk causal mask (a chunk token sees earlier chunk
tokens, written in this same dispatch), the cross-request isolation
(different requests own disjoint physical blocks) and the window.
Speculative VERIFY rows (k fed tokens at consecutive tail positions of one
request) are the same packing as a k-token prefill chunk — no kernel changes
needed for speculative decoding; the engine's acceptance rule consumes the
per-position logits downstream.
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


# A copy group covers at least this many KV tokens, and its double-buffered
# pages (K and V, and their scale rows when quantized) fit this much VMEM.
GROUP_TOKENS = 128
GROUP_VMEM_BYTES = 8 * 2 ** 20


def pages_per_group(block_size: int, num_kv_heads: int, head_dim: int,
                    kv_itemsize: int, quantized: bool) -> int:
    """Pages the ragged kernel copies in one group: enough for
    ``GROUP_TOKENS`` KV tokens, fewer where two buffers of K and V pages
    (head_dim padded to 128 lanes, and a 128-lane f32 row of scales per
    slot when quantized) would pass ``GROUP_VMEM_BYTES``.  Depends on
    shapes only, so the serving engine's counters
    (``serving.engine.attention_grid``) use it too."""
    lanes = -(-head_dim // 128) * 128
    per_slot = lanes * kv_itemsize + (128 * 4 if quantized else 0)
    page = 2 * 2 * num_kv_heads * block_size * per_slot   # K+V, 2 buffers
    return max(1, min(-(-GROUP_TOKENS // block_size),
                      GROUP_VMEM_BYTES // page))


def lane_bounds(qp, live, block_size: int, window: int | None, xp=jnp):
    """Logical blocks ``[first, last)`` a token at position ``qp`` can see
    in a row of ``live`` blocks: causal (``last`` stops at the block that
    holds ``qp``), windowed (``first`` is the block that holds the oldest
    visible position), and empty for a pad lane (``qp`` = -1).  ``xp`` is
    ``jnp`` in the kernel and ``numpy`` where the serving engine counts
    what the kernel walks (``serving.engine.attention_grid``)."""
    last = xp.where(qp < 0, 0, xp.minimum(live, qp // block_size + 1))
    first = 0
    if window is not None:
        first = xp.maximum(qp - window + 1, 0) // block_size
    return first, last


def _lanes(scales, width: int):
    """A (n, 128) tile of per-slot scales, each repeated across its row,
    widened to ``width`` lanes (a multiple of 128)."""
    return jnp.tile(scales, (1, width // 128)) if width > 128 else scales


def _ragged_kernel(rows_ref, bt_ref, qpos_ref, nblk_ref, q_ref, k_hbm, v_hbm,
                   *rest, scale: float, softcap: float | None,
                   window: int | None, block_size: int, pages: int,
                   quantized: bool):
    if quantized:
        ks_hbm, vs_hbm, o_ref, kbuf, vbuf, ksbuf, vsbuf, sems = rest
        srcs, bufs = (k_hbm, v_hbm, ks_hbm, vs_hbm), (kbuf, vbuf, ksbuf, vsbuf)
    else:
        o_ref, kbuf, vbuf, sems = rest
        srcs, bufs = (k_hbm, v_hbm), (kbuf, vbuf)
    K, G, Dp = q_ref.shape                    # head_dim padded to 128 lanes
    D = o_ref.shape[-1]
    span = pages * block_size
    t = pl.program_id(0)
    row = rows_ref[t]
    qp = qpos_ref[t]
    first, last = lane_bounds(qp, nblk_ref[row], block_size, window)
    n_groups = (jnp.maximum(last - first, 0) + pages - 1) // pages

    def copies(g, slot):
        """Group g's page copies into buffer ``slot``, each with the
        condition that its logical block is one the lane walks: a page
        past ``last`` is neither copied nor waited for."""
        out = []
        for i in range(pages):
            j = first + g * pages + i
            page = bt_ref[row, jnp.minimum(j, last - 1)]
            out.append((j < last, [
                pltpu.make_async_copy(src.at[page], buf.at[slot, :, i],
                                      sems.at[slot])
                for src, buf in zip(srcs, bufs)]))
        return out

    def start(g, slot):
        for live, cps in copies(g, slot):
            @pl.when(live)
            def _():
                for c in cps:
                    c.start()

    def wait(g, slot):
        for live, cps in copies(g, slot):
            @pl.when(live)
            def _():
                for c in cps:
                    c.wait()

    @pl.when(n_groups > 0)
    def _prefetch():
        start(0, 0)

    def body(g, carry):
        slot = g % 2

        @pl.when(g + 1 < n_groups)
        def _next():
            start(g + 1, 1 - slot)

        wait(g, slot)
        # logical block first + g*P + i covers absolute positions
        # [(first + g*P + i)*bs, ...): masking is positional, so the group's
        # tail past ``last`` (not copied: stale VMEM) and positions past qp
        # or before the window vanish here.
        base = (first + g * pages) * block_size
        kpos = base + jax.lax.broadcasted_iota(jnp.int32, (1, span), 1)
        mask = (kpos <= qp) & (kpos < last * block_size)      # (1, P*bs)
        if window is not None:
            mask &= (qp - kpos) < window
        # stale slots may hold anything, NaN included (0 * NaN is NaN):
        # their V rows are zeroed
        copied = (base + jax.lax.broadcasted_iota(jnp.int32, (span, 1), 0)
                  < last * block_size)                        # (P*bs, 1)
        out = []
        for h in range(K):
            m_prev, l_prev, acc_prev = carry[h]
            q = q_ref[h].astype(jnp.float32)                  # (G, Dp)
            k = kbuf[slot, h].astype(jnp.float32).reshape(span, Dp)
            if quantized:
                k = k * _lanes(ksbuf[slot, h].reshape(span, 128), Dp)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = s * scale
            if softcap is not None:
                s = softcap * jnp.tanh(s / softcap)
            s = jnp.where(mask, s, NEG_INF)                   # (G, P*bs)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            # explicit re-mask: when EVERY position is masked (window start
            # of a live group), s - m_new is NEG_INF - NEG_INF = 0 and exp
            # would emit 1s; zeroing by mask keeps l exact so the end can
            # guard on l == 0.
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            v = vbuf[slot, h].astype(jnp.float32).reshape(span, Dp)
            if quantized:
                v = v * _lanes(vsbuf[slot, h].reshape(span, 128), Dp)
            v = jnp.where(copied, v, 0.0)
            pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            out.append((m_new,
                        alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True),
                        acc_prev * alpha + pv))
        return tuple(out)

    init = tuple((jnp.full((G, 1), NEG_INF, jnp.float32),
                  jnp.zeros((G, 1), jnp.float32),
                  jnp.zeros((G, Dp), jnp.float32)) for _ in range(K))
    state = jax.lax.fori_loop(0, n_groups, body, init)
    for h in range(K):
        _, l, acc = state[h]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[h] = (acc / l)[:, :D].astype(o_ref.dtype)


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
                               interpret: bool = False):
    """q: (T,H,D) packed tokens; pools (N,bs,K,D); block_tables (R,nb) int32,
    -1 = unused; row_ids (T,) request row of each token (-1 = pad lane);
    token_pos (T,) absolute position of each token (-1 = pad lane).

    Grid (T,): one step per packed token, all of its query heads at once.
    The step walks the logical blocks ``[first, last)`` its mask can keep
    (``lane_bounds``: causal, windowed, none for a pad lane) in groups of
    ``pages_per_group`` pages, copying each page's (K, bs, D) slab of K and
    V from HBM into a double-buffered VMEM scratch and starting group i+1
    before computing group i.  Pad lanes (row -1 or position -1) take no
    trips and write exact zeros.

    ``k_scale``/``v_scale`` (N,bs,K) f32, when given, mark the pool as
    quantized (int8/fp8 leaves): their tiles follow each page, and the
    kernel dequantizes in VMEM, so HBM only ever streams the narrow bytes.

    Mosaic copies only whole 128-lane rows out of HBM, so head_dim is padded
    to a multiple of 128 (``Dp``; the pool's tiled layout pads it so
    already) and each scale is repeated across a 128-lane row.

    The ops' metadata names two phases: ``kv_pool/layout``, the pool's
    transposes into the kernel's (N,K,bs,Dp) order, and ``attention``, the
    rest; the custom call is named ``ragged_paged_attention``."""
    T, H, D = q.shape
    N, bs, K, _ = k_pool.shape
    G = H // K
    Dp = pl.cdiv(D, 128) * 128
    quantized = k_scale is not None
    P = pages_per_group(bs, K, D, k_pool.dtype.itemsize, quantized)
    with jax.named_scope("attention"):
        bt = jnp.maximum(block_tables, 0).astype(jnp.int32)
        rows = jnp.clip(row_ids, 0,
                        block_tables.shape[0] - 1).astype(jnp.int32)
        # a lane without a row is a pad lane whatever its position
        qpos = jnp.where(row_ids < 0, -1, token_pos).astype(jnp.int32)
        # per-row live-block counts (tables are dense prefixes: valid
        # entries precede every -1)
        nblk = jnp.sum(block_tables >= 0, axis=1).astype(jnp.int32)
        qh = jnp.pad(q.reshape(T, K, G, D), ((0, 0),) * 3 + ((0, Dp - D),))
    with jax.named_scope("kv_pool"), jax.named_scope("layout"):
        # (N,bs,K,D) → (N,K,bs,Dp) as a product with a (D, Dp) identity:
        # exact, and one pass over the pool, where XLA would give a pad
        # after a transpose a pass of its own
        eye = jnp.eye(D, Dp, dtype=k_pool.dtype)
        kt, vt = (jnp.einsum("nbkd,de->nkbe", pool, eye,
                             precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=pool.dtype)
                  for pool in (k_pool, v_pool))
        # (N,bs,K) → (N,K,bs,128): a page's scales for each head, one per
        # row, multiplying its K or V rows in the kernel
        scales = [jnp.broadcast_to(s.transpose(0, 2, 1)[..., None],
                                   (N, K, bs, 128))
                  for s in ((k_scale, v_scale) if quantized else ())]

    kern = functools.partial(_ragged_kernel, scale=scale, softcap=softcap,
                             window=window, block_size=bs, pages=P,
                             quantized=quantized)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    page_bufs = [pltpu.VMEM((2, K, P, bs, Dp), k_pool.dtype)] * 2
    if quantized:
        page_bufs += [pltpu.VMEM((2, K, P, bs, 128), jnp.float32)] * 2
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,                        # rows, bt, qp, nblk
        grid=(T,),
        in_specs=[pl.BlockSpec((None, K, G, Dp), lambda t, *_: (t, 0, 0, 0))]
        + [hbm] * (2 + len(scales)),
        out_specs=pl.BlockSpec((None, K, G, D), lambda t, *_: (t, 0, 0, 0)),
        scratch_shapes=page_bufs + [pltpu.SemaphoreType.DMA((2,))],
    )
    with jax.named_scope("attention"):
        out = pl.pallas_call(
            kern, grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((T, K, G, D), q.dtype),
            interpret=interpret, name="ragged_paged_attention",
        )(rows, bt, qpos, nblk, qh, kt, vt, *scales)
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
