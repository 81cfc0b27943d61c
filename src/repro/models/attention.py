"""GQA attention with per-layer window / softcap / qk-norm, plus KV caches.

Three execution backends:
- ``xla``              — chunked (flash-style) pure-JAX path: scan over query
                          chunks so the (S×S) score matrix is never
                          materialized; this is what the dry-run lowers.
- ``pallas``           — the Pallas TPU kernel (kernels/flash_attention).
- ``pallas_interpret`` — same kernel, interpret mode (CPU validation).

``cfg.attn_backend = None`` (the default) lets the platform choose for the
paged tick (``paged_attn_backend``): the compiled ragged kernel on a TPU,
the XLA gather path elsewhere; unpinned full-sequence attention stays on
``xla``.  Interpret mode is a CPU test tool and raises on a TPU.

Cache layout: ``{"k": (B, S_c, K, D), "v": (B, S_c, K, D), "pos": (B, S_c)}``
where ``pos`` holds the absolute position stored in each slot (-1 = empty).
Local-window layers use a ring buffer (S_c = window); the pos array makes
ring semantics trivial: a slot is visible iff 0 ≤ q_pos - slot_pos < window.
RoPE is applied before caching, so cached keys are already rotated.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention import quant as da_quant

from .config import LayerSpec, ModelConfig
from .layers import dense_init, dtype_of, rmsnorm, rmsnorm_axes, rmsnorm_init, rope, softcap

NEG_INF = -1e30


# ------------------------------------------------------------------ params
def attn_init(key, cfg: ModelConfig, *, d_in: int | None = None,
              d_out: int | None = None) -> dict:
    d_in = d_in or cfg.d_model
    d_out = d_out or cfg.d_model
    H, K, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt = dtype_of(cfg)
    kq, kk, kv, ko = jax.random.split(key, 4)
    p = {
        "wq": dense_init(kq, (d_in, H, D), dt),
        "wk": dense_init(kk, (d_in, K, D), dt),
        "wv": dense_init(kv, (d_in, K, D), dt),
        "wo": dense_init(ko, (H, D, d_out), dt, in_axis=0),
    }
    if cfg.qk_norm:
        p["q_norm"] = rmsnorm_init(D, dt)
        p["k_norm"] = rmsnorm_init(D, dt)
    return p


def attn_axes(cfg: ModelConfig) -> dict:
    p = {
        "wq": ("embed", "heads", None),
        "wk": ("embed", "kv_heads", None),
        "wv": ("embed", "kv_heads", None),
        "wo": ("heads", None, "embed"),
    }
    if cfg.qk_norm:
        p["q_norm"] = rmsnorm_axes()
        p["k_norm"] = rmsnorm_axes()
    return p


# ------------------------------------------------------------- core attend
def _gqa_scores(q: jax.Array, k: jax.Array, scale: float,
                cap: float | None) -> jax.Array:
    """q: (B,T,K,G,D), k: (B,S,K,D) → scores (B,K,G,T,S) in f32."""
    s = jnp.einsum("btkgd,bskd->bkgts", q, k,
                   preferred_element_type=jnp.float32) * scale
    if cap is not None:
        s = cap * jnp.tanh(s / cap)
    return s


def _masked_softmax(scores: jax.Array, mask: jax.Array) -> jax.Array:
    scores = jnp.where(mask, scores, NEG_INF)
    m = jnp.max(scores, axis=-1, keepdims=True)
    e = jnp.exp(scores - jax.lax.stop_gradient(m))
    z = jnp.sum(e, axis=-1, keepdims=True)
    return e / z


def _attend(q, k, v, q_pos, k_pos, *, window: int | None, cap: float | None,
            scale: float) -> jax.Array:
    """q: (B,T,H,D) vs k/v: (B,S,K,D); positions give causality + window.
    Returns (B,T,H,D)."""
    B, T, H, D = q.shape
    K = k.shape[2]
    G = H // K
    qh = q.reshape(B, T, K, G, D)
    scores = _gqa_scores(qh, k, scale, cap)                       # (B,K,G,T,S)
    mask = (k_pos[:, None, :] <= q_pos[:, :, None]) & (k_pos[:, None, :] >= 0)
    if window is not None:
        mask &= (q_pos[:, :, None] - k_pos[:, None, :]) < window
    probs = _masked_softmax(scores, mask[:, None, None, :, :])
    out = jnp.einsum("bkgts,bskd->btkgd", probs.astype(v.dtype), v)
    return out.reshape(B, T, H, D)


def _attend_chunked(q, k, v, q_pos, k_pos, *, window, cap, scale, q_chunk):
    """Scan over query chunks — flash-style memory behavior in pure XLA."""
    B, S, H, D = q.shape
    if S <= q_chunk:
        return _attend(q, k, v, q_pos, k_pos, window=window, cap=cap, scale=scale)
    pad = (-S) % q_chunk
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, pad)), constant_values=-1)
    n_chunks = q.shape[1] // q_chunk
    qs = q.reshape(B, n_chunks, q_chunk, H, D).transpose(1, 0, 2, 3, 4)
    ps = q_pos.reshape(B, n_chunks, q_chunk).transpose(1, 0, 2)

    def body(_, qc):
        qi, pi = qc
        # NOTE: with a static window we could slice k/v around the chunk; we
        # keep full-K per chunk for GSPMD friendliness and mask instead.
        out = _attend(qi, k, v, pi, k_pos, window=window, cap=cap, scale=scale)
        return None, out

    # Flash-attention memory discipline: recompute chunk scores/probs in the
    # backward instead of letting scan stash the (B,H,qc,S) f32 probs for
    # EVERY chunk (which costs ~n_chunks × score-matrix per layer and was the
    # dominant train-step buffer — §Perf llama4 iteration C3).
    body = jax.checkpoint(body)
    _, outs = jax.lax.scan(body, None, (qs, ps))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(B, n_chunks * q_chunk, H, D)
    return out[:, :S]


def _ragged_attend_chunked(q, kd, vd, k_pos, q_pos, rows, *, window, cap,
                           scale, q_chunk):
    """Packed-token attention over per-request densified caches, scanned in
    token chunks so the (chunk, L, K, D) per-token KV gather — not the full
    (T, L, K, D) expansion — is the largest buffer.

    q: (T,H,D) packed tokens; kd/vd: (R,L,K,D) densified per request row;
    k_pos: (R,L) absolute positions (-1 empty); q_pos (T,); rows (T,) request
    row per token, already clamped to [0,R).  Pad lanes (q_pos = -1) mask
    every position and emit garbage that callers ignore."""
    T, H, D = q.shape
    if T <= q_chunk:
        return _attend(q[:, None], kd[rows], vd[rows], q_pos[:, None],
                       k_pos[rows], window=window, cap=cap, scale=scale)[:, 0]
    pad = (-T) % q_chunk
    if pad:
        q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, (0, pad), constant_values=-1)
        rows = jnp.pad(rows, (0, pad))
    n_chunks = q.shape[0] // q_chunk
    qs = q.reshape(n_chunks, q_chunk, H, D)
    ps = q_pos.reshape(n_chunks, q_chunk)
    rs = rows.reshape(n_chunks, q_chunk)

    def body(_, xs):
        qi, pi, ri = xs
        out = _attend(qi[:, None], kd[ri], vd[ri], pi[:, None], k_pos[ri],
                      window=window, cap=cap, scale=scale)
        return None, out[:, 0]

    _, outs = jax.lax.scan(body, None, (qs, ps, rs))
    return outs.reshape(n_chunks * q_chunk, H, D)[:T]


# ------------------------------------------------------------------- cache
def init_cache(cfg: ModelConfig, spec: LayerSpec, batch: int, max_len: int) -> dict:
    S_c = min(spec.window, max_len) if spec.window else max_len
    K, D = cfg.n_kv_heads, cfg.head_dim
    dt = dtype_of(cfg)
    return {
        "k": jnp.zeros((batch, S_c, K, D), dtype=dt),
        "v": jnp.zeros((batch, S_c, K, D), dtype=dt),
        "pos": jnp.full((batch, S_c), -1, dtype=jnp.int32),
    }


def cache_axes() -> dict:
    return {"k": ("cache_batch", "cache_seq", "kv_heads", None),
            "v": ("cache_batch", "cache_seq", "kv_heads", None),
            "pos": ("cache_batch", "cache_seq")}


def _cache_write(cache: dict, k_new, v_new, positions) -> dict:
    """Scatter T new entries at slots pos % S_c (ring for local layers)."""
    B, S_c = cache["pos"].shape
    T = positions.shape[1]
    slots = positions % S_c                                  # (B, T)
    bidx = jnp.arange(B)[:, None]
    return {
        "k": cache["k"].at[bidx, slots].set(k_new),
        "v": cache["v"].at[bidx, slots].set(v_new),
        "pos": cache["pos"].at[bidx, slots].set(positions),
    }


# ------------------------------------------------------------------ paging
def init_paged_pool(cfg: ModelConfig, num_blocks: int, block_size: int,
                    kv_dtype: str | None = None) -> dict:
    """One layer's share of the global KV block pool.

    Unlike the dense per-slot cache there is no batch axis and no "pos" leaf:
    blocks are a flat pool shared by every request, and the absolute position
    of slot p in a request's logical block j is implicit (j·bs + p), fixed by
    the request's block table.  Local-window layers use the same full-length
    pool and mask positionally (a paged ring would forbid block sharing).

    ``kv_dtype`` (default ``cfg.kv_dtype``) picks the storage dtype; int8 /
    fp8_e4m3 add ``k_scale``/``v_scale`` leaves — one f32 scale per (block,
    slot, kv-head), quantize-on-write in the block writers below.  Scales
    init to 1 so untouched blocks (incl. the reserved null block) dequantize
    to exact zeros."""
    K, D = cfg.n_kv_heads, cfg.head_dim
    kv_dtype = cfg.kv_dtype if kv_dtype is None else kv_dtype
    dt = da_quant.storage_dtype(kv_dtype, dtype_of(cfg))
    pool = {"k": jnp.zeros((num_blocks, block_size, K, D), dtype=dt),
            "v": jnp.zeros((num_blocks, block_size, K, D), dtype=dt)}
    if da_quant.is_quantized(kv_dtype):
        pool["k_scale"] = jnp.ones((num_blocks, block_size, K), jnp.float32)
        pool["v_scale"] = jnp.ones((num_blocks, block_size, K), jnp.float32)
    return pool


def paged_pool_axes(cfg: ModelConfig, kv_dtype: str | None = None) -> dict:
    """Logical axes for one layer's paged-pool leaves (mirrors
    ``init_paged_pool``): the block and slot dims stay UNSHARDED — block
    tables are host-side and every device must be able to scatter any
    (block, slot) — so ``kv_heads`` is the one shardable dim, the same
    model-axis split the attention weights use.  Scale leaves carry the
    same (block, slot, kv-head) layout minus the head_dim."""
    kv_dtype = cfg.kv_dtype if kv_dtype is None else kv_dtype
    kv = (None, None, "kv_heads", None)
    axes = {"k": kv, "v": kv}
    if da_quant.is_quantized(kv_dtype):
        axes["k_scale"] = (None, None, "kv_heads")
        axes["v_scale"] = (None, None, "kv_heads")
    return axes


def _dequant_pool_leaves(pool: dict, dtype):
    """K/V leaves at the model's activation ``dtype`` for the XLA densify
    fallback (identity when the pool is unquantized), so attention returns
    the dtype it was given, as the kernel does.  The fallback materializes
    a dequantized pool copy — acceptable off-TPU; the Pallas path
    dequantizes in-register instead."""
    if "k_scale" not in pool:
        return pool["k"], pool["v"]
    return (da_quant.dequantize_kv(pool["k"], pool["k_scale"]).astype(dtype),
            da_quant.dequantize_kv(pool["v"], pool["v_scale"]).astype(dtype))


def _quantize_for_pool(pool: dict, k_new, v_new):
    """Quantize new K/V entries to the pool's storage dtype (identity for
    unquantized pools).  Per-token-per-head scales mean a written token's
    bytes depend only on that token — rewrites (chunked prefill, rollback,
    migration scatter) never requantize neighbours, which is what keeps
    greedy streams bit-identical across spill/adopt/preempt/resume."""
    if "k_scale" not in pool:
        return k_new, v_new, None, None
    name = "int8" if pool["k"].dtype == jnp.int8 else "fp8_e4m3"
    kq, ks = da_quant.quantize_kv(k_new, name)
    vq, vs = da_quant.quantize_kv(v_new, name)
    return kq, vq, ks, vs


def _paged_write(pool: dict, k_new, v_new, positions, block_table) -> dict:
    """Scatter T new K/V entries into pool blocks via the block table.

    positions: (B,T) absolute; block_table: (B,nb) physical ids, -1 unused.
    Pad entries clamp to block 0 — the allocator's reserved null block — so
    masked rows (inactive decode slots) scribble harmlessly there."""
    bs = pool["k"].shape[1]
    blk = jnp.take_along_axis(block_table, positions // bs, axis=1)
    blk = jnp.maximum(blk, 0)                                # (B, T)
    slot = positions % bs
    k_new, v_new, ks, vs = _quantize_for_pool(pool, k_new, v_new)
    out = {"k": pool["k"].at[blk, slot].set(k_new.astype(pool["k"].dtype)),
           "v": pool["v"].at[blk, slot].set(v_new.astype(pool["v"].dtype))}
    if ks is not None:
        out["k_scale"] = pool["k_scale"].at[blk, slot].set(ks)
        out["v_scale"] = pool["v_scale"].at[blk, slot].set(vs)
    return out


def _ragged_paged_write(pool: dict, k_new, v_new, positions, block_table,
                        row_ids) -> dict:
    """Scatter a PACKED token batch's K/V into pool blocks: token t lands in
    its own request's block, resolved per token through ``row_ids``.

    k_new/v_new: (T,K,D); positions (T,) absolute (-1 = pad); block_table
    (R,nb); row_ids (T,) request row per token (-1 = pad).  Pad lanes clamp
    to block 0 (the reserved null block) and scribble harmlessly there."""
    bs = pool["k"].shape[1]
    rows = jnp.clip(row_ids, 0, block_table.shape[0] - 1)
    posc = jnp.maximum(positions, 0)
    blk = block_table[rows, posc // bs]                      # (T,)
    valid = (row_ids >= 0) & (positions >= 0)
    blk = jnp.where(valid, jnp.maximum(blk, 0), 0)
    slot = jnp.where(valid, posc % bs, 0)
    k_new, v_new, ks, vs = _quantize_for_pool(pool, k_new, v_new)
    out = {"k": pool["k"].at[blk, slot].set(k_new.astype(pool["k"].dtype)),
           "v": pool["v"].at[blk, slot].set(v_new.astype(pool["v"].dtype))}
    if ks is not None:
        out["k_scale"] = pool["k_scale"].at[blk, slot].set(ks)
        out["v_scale"] = pool["v_scale"].at[blk, slot].set(vs)
    return out


# ---------------------------------------------------------------- backends
def _checked_backend(backend: str) -> str:
    if backend == "pallas_interpret" and jax.default_backend() == "tpu":
        raise ValueError(
            "attn_backend='pallas_interpret' runs the Pallas kernels in the "
            "CPU interpreter; on a TPU leave attn_backend unset (or 'pallas') "
            "so the compiled kernel runs")
    return backend


def paged_attn_backend(cfg: ModelConfig) -> str:
    """The paged tick's attention backend: ``cfg.attn_backend`` when pinned,
    else the platform's — the compiled ragged kernel on a TPU, the XLA
    densify path on every other backend."""
    if cfg.attn_backend is not None:
        return _checked_backend(cfg.attn_backend)
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def _ragged_kernel_attention(q, pool: dict, block_table, row_ids, positions,
                             *, window, cap, scale, interpret: bool):
    """The ragged paged kernel over packed tokens q (T,H,D).

    Inside a mesh context (a mesh-sliced replica's tick) the call runs under
    ``shard_map``: Mosaic kernels cannot be partitioned automatically.  Each
    shard runs the kernel on its own kv heads and the query heads that read
    them — 'model' splits heads and kv_heads into contiguous equal runs, so
    shard s's H/m query heads are exactly the G-groups of its K/m kv heads.
    Block table, row ids and positions are replicated.  When kv_heads does
    not divide over 'model' the pool is replicated and so is the call."""
    from jax.sharding import PartitionSpec as P

    from repro.kernels.decode_attention import ops as da_ops

    names = [n for n in ("k", "v", "k_scale", "v_scale") if n in pool]

    def call(q, bt, rows, pos, *leaves):
        p = dict(zip(names, leaves))
        return da_ops.ragged_paged_attention(
            q, p["k"], p["v"], bt, rows, pos, k_scale=p.get("k_scale"),
            v_scale=p.get("v_scale"), window=window, softcap=cap,
            scale=scale, interpret=interpret)

    args = (q, block_table, row_ids, positions, *(pool[n] for n in names))
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return call(*args)
    m = dict(mesh.shape).get("model", 1)
    ax = "model" if pool["k"].shape[2] % m == 0 else None
    leaf = {"k": P(None, None, ax, None), "v": P(None, None, ax, None),
            "k_scale": P(None, None, ax), "v_scale": P(None, None, ax)}
    q_spec = P(None, ax, None)
    in_specs = (q_spec, P(), P(), P(), *(leaf[n] for n in names))
    return jax.shard_map(call, in_specs=in_specs, out_specs=q_spec,
                         check_vma=False)(*args)


# ------------------------------------------------------------------- apply
def _qkv(params: dict, x: jax.Array, positions: jax.Array, *,
         cfg: ModelConfig, spec: LayerSpec):
    """Shared projection + qk-norm + RoPE front end of every attention path."""
    q = jnp.einsum("btd,dhk->bthk", x, params["wq"])
    k = jnp.einsum("btd,dhk->bthk", x, params["wk"])
    v = jnp.einsum("btd,dhk->bthk", x, params["wv"])
    if cfg.qk_norm:
        q = rmsnorm(params["q_norm"], q)
        k = rmsnorm(params["k_norm"], k)
    q = rope(q, positions, spec.rope_theta)
    k = rope(k, positions, spec.rope_theta)
    return q, k, v


def attention(params: dict, x: jax.Array, positions: jax.Array, *,
              cfg: ModelConfig, spec: LayerSpec,
              cache: dict | None = None) -> tuple[jax.Array, dict | None]:
    """Full attention block (projections included).

    Without a cache: causal self-attention over x (train / scoring).
    With a cache: write this step's k/v then attend over the cache
    (decode: T=1; prefill-into-cache: T=S).
    """
    B, T, _ = x.shape
    q, k, v = _qkv(params, x, positions, cfg=cfg, spec=spec)
    scale = cfg.head_dim ** -0.5
    cap = cfg.attn_logit_softcap

    if cache is not None:
        cache = _cache_write(cache, k, v, positions)
        out = _attend(q, cache["k"], cache["v"], positions, cache["pos"],
                      window=spec.window, cap=cap, scale=scale)
    else:
        backend = _checked_backend(cfg.attn_backend or "xla")
        if backend in ("pallas", "pallas_interpret"):
            from repro.kernels.flash_attention import ops as fa_ops
            out = fa_ops.flash_attention(
                q, k, v, positions=positions, window=spec.window,
                softcap=cap, scale=scale,
                interpret=(backend == "pallas_interpret"))
        else:
            out = _attend_chunked(q, k, v, positions, positions,
                                  window=spec.window, cap=cap, scale=scale,
                                  q_chunk=cfg.q_chunk)
    y = jnp.einsum("bthk,hkd->btd", out, params["wo"])
    return y, cache


def prefill_cache(params: dict, x: jax.Array, positions: jax.Array, *,
                  cfg: ModelConfig, spec: LayerSpec, max_len: int
                  ) -> tuple[jax.Array, dict]:
    """Run attention over the prompt AND build the layer's decode cache."""
    B, S, _ = x.shape
    cache = init_cache(cfg, spec, B, max_len)
    q, k, v = _qkv(params, x, positions, cfg=cfg, spec=spec)
    out = _attend_chunked(q, k, v, positions, positions,
                          window=spec.window, cap=cfg.attn_logit_softcap,
                          scale=cfg.head_dim ** -0.5, q_chunk=cfg.q_chunk)
    cache = _cache_write(cache, k, v, positions)
    y = jnp.einsum("bthk,hkd->btd", out, params["wo"])
    return y, cache


def paged_attention(params: dict, x: jax.Array, positions: jax.Array, *,
                    cfg: ModelConfig, spec: LayerSpec, pool: dict,
                    block_table: jax.Array,
                    row_ids: jax.Array | None = None
                    ) -> tuple[jax.Array, dict]:
    """Attention against the paged KV pool: write x's K/V into this request's
    blocks, then attend over everything the block table maps — which includes
    any prefix blocks shared with other requests.

    Batched mode (``row_ids is None``, x row b ↔ block_table row b):
    - suffix prefill (T = S - reused_len): tokens enter at positions starting
      past the reused prefix and attend to the cached prefix KV for free;
    - decode (T = 1): the Pallas block-gather kernel when the backend
      (``paged_attn_backend``) is pallas/pallas_interpret, else an XLA
      gather + masked softmax.

    Ragged mode (``row_ids`` given): x is ONE packed row (B = 1) of mixed
    prefill-chunk and decode tokens — the engine's unified token-budget tick.
    Token t belongs to request row ``row_ids[t]`` of the block table (-1 =
    pad lane); all K/V is written first, then every token attends causally at
    its own position, so a chunk token sees its same-dispatch predecessors
    and any same-tick sibling's shared prefix blocks, while pad lanes scribble
    only the null block.
    """
    B, T, _ = x.shape
    K, D = cfg.n_kv_heads, cfg.head_dim
    q, k, v = _qkv(params, x, positions, cfg=cfg, spec=spec)
    scale = D ** -0.5
    cap = cfg.attn_logit_softcap
    backend = paged_attn_backend(cfg)
    if row_ids is not None:
        pool = _ragged_paged_write(pool, k[0], v[0], positions[0],
                                   block_table, row_ids)
        if backend in ("pallas", "pallas_interpret"):
            out = _ragged_kernel_attention(
                q[0], pool, block_table, row_ids, positions[0],
                window=spec.window, cap=cap, scale=scale,
                interpret=(backend == "pallas_interpret"))[None]
        else:
            from repro.kernels.decode_attention.ref import densify_pool
            kp, vp = _dequant_pool_leaves(pool, q.dtype)
            kd, vd, kpos = densify_pool(kp, vp, block_table)
            rows = jnp.clip(row_ids, 0, block_table.shape[0] - 1)
            out = _ragged_attend_chunked(
                q[0], kd, vd, kpos, positions[0], rows, window=spec.window,
                cap=cap, scale=scale, q_chunk=cfg.q_chunk)[None]
        y = jnp.einsum("bthk,hkd->btd", out, params["wo"])
        return y, pool
    pool = _paged_write(pool, k, v, positions, block_table)
    if T == 1 and backend in ("pallas", "pallas_interpret"):
        from repro.kernels.decode_attention import ops as da_ops
        out = da_ops.paged_decode_attention(
            q[:, 0], pool["k"], pool["v"], block_table, positions[:, 0],
            k_scale=pool.get("k_scale"), v_scale=pool.get("v_scale"),
            window=spec.window, softcap=cap, scale=scale,
            interpret=(backend == "pallas_interpret"))[:, None]
    else:
        from repro.kernels.decode_attention.ref import densify_pool
        kp, vp = _dequant_pool_leaves(pool, q.dtype)
        kd, vd, kpos = densify_pool(kp, vp, block_table)
        # chunked for suffix prefill (T may approach max_len, and the full
        # (B,K,G,T,nb*bs) f32 score tensor is the dominant buffer exactly as
        # in dense prefill); decode's T=1 short-circuits to plain _attend
        out = _attend_chunked(q, kd, vd, positions, kpos, window=spec.window,
                              cap=cap, scale=scale, q_chunk=cfg.q_chunk)
    y = jnp.einsum("bthk,hkd->btd", out, params["wo"])
    return y, pool
