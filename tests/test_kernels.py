"""Pallas kernel validation (interpret mode) vs pure-jnp oracles: shape/dtype
sweeps + seeded deterministic parameter sweeps (the former hypothesis draws,
pinned so the suite needs no extra dependency)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention.ops import (decode_attention,
                                                paged_decode_attention,
                                                ragged_paged_attention)
from repro.kernels.decode_attention.ref import (decode_attention_ref,
                                                densify_pool,
                                                paged_decode_attention_ref,
                                                ragged_paged_attention_ref)
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.ssd.ops import ssd
from repro.kernels.ssd.ref import ssd_ref


# ------------------------------------------------------------ flash attention
FLASH_CASES = [
    # (B, S, H, K, D, window, softcap, dtype)
    (2, 128, 4, 2, 64, None, None, jnp.float32),
    (1, 256, 4, 4, 64, 64, None, jnp.float32),
    (2, 100, 8, 2, 32, None, 50.0, jnp.float32),
    (1, 96, 4, 1, 64, 32, 30.0, jnp.float32),
    (1, 64, 2, 2, 128, None, None, jnp.bfloat16),
    (1, 80, 8, 4, 16, 16, None, jnp.bfloat16),
]


@pytest.mark.parametrize("B,S,H,K,D,win,cap,dt", FLASH_CASES)
def test_flash_attention_matches_ref(B, S, H, K, D, win, cap, dt):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), dt)
    k = jax.random.normal(ks[1], (B, S, K, D), dt)
    v = jax.random.normal(ks[2], (B, S, K, D), dt)
    out = flash_attention(q, k, v, window=win, softcap=cap, interpret=True,
                          block_q=32, block_k=32)
    ref = attention_ref(q, k, v, window=win, softcap=cap)
    tol = 2e-5 if dt == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


# deterministic draws from the former hypothesis domains:
# s in [2,5], h in {2,4}, g in {1,2} with g|h, win in {None,8,24}, blk in {16,32}
FLASH_SWEEP = [
    (2, 2, 1, None, 16),
    (3, 4, 2, 8, 32),
    (4, 2, 2, 24, 16),
    (5, 4, 1, None, 32),
    (2, 4, 2, 24, 32),
    (5, 2, 1, 8, 16),
    (3, 2, 2, None, 32),
    (4, 4, 1, 24, 16),
]


@pytest.mark.parametrize("s,h,g,win,blk", FLASH_SWEEP)
def test_flash_attention_param_sweep(s, h, g, win, blk):
    B, S, D = 1, s * 16, 32
    K = h // g
    ks = jax.random.split(jax.random.PRNGKey(s * 7 + h), 3)
    q = jax.random.normal(ks[0], (B, S, h, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, K, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, K, D), jnp.float32)
    out = flash_attention(q, k, v, window=win, interpret=True,
                          block_q=blk, block_k=blk)
    ref = attention_ref(q, k, v, window=win)
    np.testing.assert_allclose(out, ref, atol=3e-5, rtol=3e-5)


# ------------------------------------------------------------ decode attention
DECODE_CASES = [
    (2, 256, 8, 2, 64, None, None, 200),
    (1, 128, 4, 4, 32, 64, None, 128),
    (2, 512, 8, 1, 64, None, 50.0, 300),
    (3, 96, 4, 2, 64, 32, 30.0, 50),
]


@pytest.mark.parametrize("B,S,H,K,D,win,cap,fill", DECODE_CASES)
def test_decode_attention_matches_ref(B, S, H, K, D, win, cap, fill):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, D), jnp.float32)
    kc = jax.random.normal(ks[1], (B, S, K, D), jnp.float32)
    vc = jax.random.normal(ks[2], (B, S, K, D), jnp.float32)
    pos = jnp.where(jnp.arange(S)[None, :] < fill, jnp.arange(S)[None, :], -1)
    pos = jnp.broadcast_to(pos, (B, S))
    qpos = jnp.full((B,), fill - 1, jnp.int32)
    out = decode_attention(q, kc, vc, qpos, pos, window=win, softcap=cap,
                           interpret=True, block_k=64)
    ref = decode_attention_ref(q, kc, vc, qpos, pos, window=win, softcap=cap)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_decode_attention_ring_buffer_semantics():
    """Ring cache: slot positions arbitrary; only in-window slots count."""
    B, S, H, K, D = 1, 64, 4, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, H, D), jnp.float32)
    kc = jax.random.normal(ks[1], (B, S, K, D), jnp.float32)
    vc = jax.random.normal(ks[2], (B, S, K, D), jnp.float32)
    # ring: slot s holds position 100 - (s % 7) scattered arbitrarily
    pos = (100 - (jnp.arange(S) % 7))[None, :]
    qpos = jnp.full((B,), 100, jnp.int32)
    out = decode_attention(q, kc, vc, qpos, pos, window=5, interpret=True,
                           block_k=16)
    ref = decode_attention_ref(q, kc, vc, qpos, pos, window=5)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


# ----------------------------------------------------- paged decode attention
# seeded sweep over (heads H, kv heads K, block size bs, cache lengths):
# each case scatters per-request caches into a shared block pool through
# randomized block tables and must match BOTH the paged oracle and the dense
# decode oracle on the densified layout.
PAGED_DECODE_SWEEP = [
    # (B, H, K, D, bs, nb, ctx_lens, window, softcap)
    (2, 4, 2, 32, 8, 4, (25, 9), None, None),
    (1, 8, 8, 64, 16, 4, (64,), None, None),
    (3, 4, 1, 64, 16, 8, (100, 17, 128), None, 30.0),
    (2, 8, 2, 32, 32, 2, (33, 64), None, None),
    (2, 4, 4, 16, 8, 8, (61, 1), 12, None),
    (1, 2, 2, 128, 64, 2, (90,), None, 50.0),
    (3, 8, 4, 32, 16, 4, (31, 32, 48), 20, None),
]


def _random_block_tables(rng, num_blocks, bs, nb, ctx_lens):
    """Distinct random physical blocks per request, -1 trailing pads;
    block 0 is kept free (the engine's reserved null block)."""
    B = len(ctx_lens)
    bt = np.full((B, nb), -1, np.int32)
    perm = rng.permutation(np.arange(1, num_blocks))
    i = 0
    for b, ctx in enumerate(ctx_lens):
        n = -(-ctx // bs)
        bt[b, :n] = perm[i:i + n]
        i += n
    return bt


@pytest.mark.parametrize("B,H,K,D,bs,nb,ctxs,win,cap", PAGED_DECODE_SWEEP)
def test_paged_decode_attention_matches_refs(B, H, K, D, bs, nb, ctxs, win, cap):
    rng = np.random.default_rng(B * 1000 + H * 10 + bs)
    N = 1 + sum(-(-c // bs) for c in ctxs) + 2          # null + used + spare
    ks = jax.random.split(jax.random.PRNGKey(B + H + bs), 3)
    q = jax.random.normal(ks[0], (B, H, D), jnp.float32)
    kp = jax.random.normal(ks[1], (N, bs, K, D), jnp.float32)
    vp = jax.random.normal(ks[2], (N, bs, K, D), jnp.float32)
    bt = jnp.asarray(_random_block_tables(rng, N, bs, nb, ctxs))
    qpos = jnp.asarray([c - 1 for c in ctxs], jnp.int32)
    out = paged_decode_attention(q, kp, vp, bt, qpos, window=win, softcap=cap,
                                 interpret=True)
    ref = paged_decode_attention_ref(q, kp, vp, bt, qpos, window=win,
                                     softcap=cap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    # cross-check vs the DENSE oracle on the densified cache: paging must be
    # a pure layout change, not a numerics change
    kd, vd, pos = densify_pool(kp, vp, bt)
    dense = decode_attention_ref(q, kd, vd, qpos, pos, window=win, softcap=cap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                               atol=2e-5, rtol=2e-5)


# ----------------------------------------------------- ragged paged attention
# the unified mixed tick's kernel: prefill CHUNKS and decode rows packed into
# one token batch.  Each case lists per-request (ctx_len, chunk_len): the
# last chunk_len positions of the context are packed as that request's
# queries (chunk_len == 1 ≡ a decode row); pad lanes fill the budget tail.
# Sweep axes per the acceptance bar: mixed chunk sizes × decode rows × block
# sizes (plus window/softcap and a shared prefix block).
RAGGED_SWEEP = [
    # (H, K, D, bs, nb, reqs=((ctx, chunk), ...), window, softcap)
    (4, 2, 32, 8, 4, ((25, 5), (9, 1)), None, None),            # chunk + decode
    (4, 4, 16, 16, 3, ((33, 33), (40, 1), (17, 1)), None, None),  # full prefill
    (8, 2, 64, 8, 8, ((61, 13), (64, 1), (30, 7), (8, 8)), None, 30.0),
    (2, 2, 128, 32, 2, ((50, 11), (33, 1)), 12, None),          # windowed
    (8, 8, 32, 16, 4, ((1, 1), (2, 1), (64, 64)), None, None),  # tiny ctxs
    (4, 1, 64, 64, 2, ((100, 36), (128, 1), (90, 2)), 20, 50.0),
    # the kernel's trip bounds, at bs 16 where a copy group is P = 8 pages:
    # a row of exactly P live blocks, and one of P + 1
    (4, 2, 32, 16, 10, ((128, 1), (140, 3), (20, 1)), None, None),
    # a chunk whose table already holds blocks past its tokens (a third
    # entry: tokens the table holds): each token stops at its own block
    (4, 2, 32, 8, 8, ((30, 12, 60), (9, 1, 40)), None, None),
    # a window whose first visible block is past block 0
    (8, 2, 32, 16, 14, ((200, 5), (150, 1)), 40, None),
    # the benchmark's head sizes: head_dim 80 (K = 8, and K = 2 as one
    # shard of a 2x2 slice sees it) and 120
    (32, 8, 80, 16, 12, ((180, 6), (33, 1), (1, 1)), 64, None),
    (8, 2, 80, 16, 9, ((140, 9), (16, 1)), None, None),
    (32, 8, 120, 16, 10, ((150, 4), (128, 1)), None, 30.0),
]


@pytest.mark.parametrize("H,K,D,bs,nb,reqs,win,cap", RAGGED_SWEEP)
def test_ragged_paged_attention_matches_refs(H, K, D, bs, nb, reqs, win, cap):
    rng = np.random.default_rng(H * 100 + bs + len(reqs))
    held = [r[2] if len(r) > 2 else r[0] for r in reqs]  # in the table
    N = 1 + sum(-(-c // bs) for c in held) + 2          # null + used + spare
    ks = jax.random.split(jax.random.PRNGKey(H + bs), 3)
    T = sum(r[1] for r in reqs) + 3                     # 3 pad lanes
    q = jax.random.normal(ks[0], (T, H, D), jnp.float32)
    kp = jax.random.normal(ks[1], (N, bs, K, D), jnp.float32)
    vp = jax.random.normal(ks[2], (N, bs, K, D), jnp.float32)
    bt = jnp.asarray(_random_block_tables(rng, N, bs, nb, held))
    rows = np.full(T, -1, np.int32)
    tpos = np.full(T, -1, np.int32)
    n = 0
    for r, (ctx, chunk, *_) in enumerate(reqs):
        rows[n:n + chunk] = r
        tpos[n:n + chunk] = np.arange(ctx - chunk, ctx)
        n += chunk
    rows, tpos = jnp.asarray(rows), jnp.asarray(tpos)
    out = ragged_paged_attention(q, kp, vp, bt, rows, tpos, window=win,
                                 softcap=cap, interpret=True)
    ref = ragged_paged_attention_ref(q, kp, vp, bt, rows, tpos, window=win,
                                     softcap=cap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    # pad lanes are EXACT zeros (the engine relies on nothing leaking there)
    assert np.all(np.asarray(out)[n:] == 0)
    # cross-check vs the independently-validated single-token paged kernel:
    # packing must be a pure layout change, token by token
    per_tok = paged_decode_attention_ref(
        q[:n], kp, vp, bt[jnp.clip(rows[:n], 0, len(reqs) - 1)], tpos[:n],
        window=win, softcap=cap)
    np.testing.assert_allclose(np.asarray(out)[:n], np.asarray(per_tok),
                               atol=2e-5, rtol=2e-5)


# ------------------------------------------------- speculative verify rows
# Multi-token VERIFY rows (speculative decoding): a decode row that feeds
# its last `fed` tokens at consecutive tail positions — fed = 1 + k draft
# tokens, k ∈ {1, 2, 4} per the acceptance bar, plus the fed = 1 (k = 0)
# degenerate case that must reproduce today's single-token decode.  Swept
# across block sizes × window/softcap, mixed with plain decode rows and a
# prefill chunk in the same packing.
VERIFY_SWEEP = [
    # (H, K, D, bs, reqs=((ctx, fed), ...), window, softcap)
    (4, 2, 32, 8, ((20, 2), (33, 3), (17, 5), (9, 1)), None, None),
    (4, 4, 16, 16, ((40, 5), (16, 2), (25, 3)), None, 30.0),
    (2, 2, 64, 32, ((50, 3), (33, 5), (9, 2), (64, 1)), 12, None),
    (8, 2, 32, 8, ((25, 5), (63, 3), (7, 2), (5, 1), (30, 12)), 16, 50.0),
    (4, 1, 64, 64, ((100, 5), (128, 2), (90, 3)), None, None),
    # head_dim 120, a verify row whose tail crosses a copy group of P = 8
    (8, 2, 120, 16, ((150, 4), (40, 3), (128, 1)), None, None),
]


@pytest.mark.parametrize("H,K,D,bs,reqs,win,cap", VERIFY_SWEEP)
def test_ragged_verify_rows_match_refs(H, K, D, bs, reqs, win, cap):
    """Verify rows are kernel-wise identical to prefill chunks of the same
    length: the ragged kernel must match the ragged oracle AND the
    independently-validated per-token paged decode oracle for every fed
    position (the logits the acceptance rule consumes)."""
    rng = np.random.default_rng(H * 31 + bs + len(reqs))
    ctxs = [c for c, _ in reqs]
    N = 1 + sum(-(-c // bs) for c in ctxs) + 2
    ks = jax.random.split(jax.random.PRNGKey(H * 7 + bs), 3)
    T = sum(f for _, f in reqs) + 2                    # 2 pad lanes
    q = jax.random.normal(ks[0], (T, H, D), jnp.float32)
    kp = jax.random.normal(ks[1], (N, bs, K, D), jnp.float32)
    vp = jax.random.normal(ks[2], (N, bs, K, D), jnp.float32)
    nb = max(-(-c // bs) for c in ctxs)
    bt = jnp.asarray(_random_block_tables(rng, N, bs, nb, ctxs))
    rows = np.full(T, -1, np.int32)
    tpos = np.full(T, -1, np.int32)
    n = 0
    for r, (ctx, fed) in enumerate(reqs):
        rows[n:n + fed] = r
        tpos[n:n + fed] = np.arange(ctx - fed, ctx)    # verify tail
        n += fed
    rows, tpos = jnp.asarray(rows), jnp.asarray(tpos)
    out = ragged_paged_attention(q, kp, vp, bt, rows, tpos, window=win,
                                 softcap=cap, interpret=True)
    ref = ragged_paged_attention_ref(q, kp, vp, bt, rows, tpos, window=win,
                                     softcap=cap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    per_tok = paged_decode_attention_ref(
        q[:n], kp, vp, bt[jnp.clip(rows[:n], 0, len(reqs) - 1)], tpos[:n],
        window=win, softcap=cap)
    np.testing.assert_allclose(np.asarray(out)[:n], np.asarray(per_tok),
                               atol=2e-5, rtol=2e-5)
    assert np.all(np.asarray(out)[n:] == 0)            # pads stay exact zeros


def test_verify_row_k0_bitmatches_single_token_decode():
    """The fed = 1 degenerate verify row IS today's decode: packing each
    request as a one-token row (with pad lanes interleaved and rows packed
    out of slot order) must BIT-match the single-token paged decode kernel
    — speculation changes the packing, never the numbers."""
    H, K, D, bs = 4, 2, 32, 8
    nb = 3
    ctxs = (21, 9, 17)
    rng = np.random.default_rng(3)
    N = 1 + sum(-(-c // bs) for c in ctxs) + 2
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    B = len(ctxs)
    q = jax.random.normal(ks[0], (B, H, D), jnp.float32)
    kp = jax.random.normal(ks[1], (N, bs, K, D), jnp.float32)
    vp = jax.random.normal(ks[2], (N, bs, K, D), jnp.float32)
    bt = jnp.asarray(_random_block_tables(rng, N, bs, nb, ctxs))
    qpos = jnp.asarray([c - 1 for c in ctxs], jnp.int32)
    decode = paged_decode_attention(q, kp, vp, bt, qpos, interpret=True)
    # scrambled one-token-per-row packing with pads: lanes [pad, 1, 0, pad, 2]
    lanes = [1, 0, 2]
    T = 5
    qr = jnp.zeros((T, H, D), jnp.float32)
    rows = np.full(T, -1, np.int32)
    tpos = np.full(T, -1, np.int32)
    for lane, b in zip((1, 2, 4), lanes):
        qr = qr.at[lane].set(q[b])
        rows[lane] = b
        tpos[lane] = int(qpos[b])
    out = ragged_paged_attention(qr, kp, vp, bt, jnp.asarray(rows),
                                 jnp.asarray(tpos), interpret=True)
    out = np.asarray(out)
    for lane, b in zip((1, 2, 4), lanes):
        assert np.array_equal(out[lane], np.asarray(decode)[b]), \
            f"lane {lane} diverged from single-token decode of request {b}"


def test_ragged_same_dispatch_shared_prefix_block():
    """Two packed chunks whose tables share a physical prefix block (the
    intra-batch sharing case) read identical prefix KV."""
    H, K, D, bs = 4, 2, 32, 8
    N, nb = 6, 2
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    T = 6
    q = jax.random.normal(ks[0], (T, H, D), jnp.float32)
    kp = jax.random.normal(ks[1], (N, bs, K, D), jnp.float32)
    vp = jax.random.normal(ks[2], (N, bs, K, D), jnp.float32)
    bt = jnp.asarray([[3, 1], [3, 2]], jnp.int32)       # block 3 shared
    rows = jnp.asarray([0, 0, 0, 1, 1, 1], jnp.int32)
    tpos = jnp.asarray([10, 11, 12, 13, 14, 15], jnp.int32)
    out = ragged_paged_attention(q, kp, vp, bt, rows, tpos, interpret=True)
    ref = ragged_paged_attention_ref(q, kp, vp, bt, rows, tpos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_paged_decode_shared_prefix_block():
    """Two requests whose tables share a physical block (trie prefix reuse)
    read identical prefix KV: outputs for the shared positions agree with a
    dense cache that duplicates the prefix."""
    B, H, K, D, bs = 2, 4, 2, 32, 8
    N, nb = 6, 2
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (B, H, D), jnp.float32)
    kp = jax.random.normal(ks[1], (N, bs, K, D), jnp.float32)
    vp = jax.random.normal(ks[2], (N, bs, K, D), jnp.float32)
    bt = jnp.asarray([[3, 1], [3, 2]], jnp.int32)       # block 3 shared
    qpos = jnp.asarray([12, 15], jnp.int32)
    out = paged_decode_attention(q, kp, vp, bt, qpos, interpret=True)
    ref = paged_decode_attention_ref(q, kp, vp, bt, qpos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


# ----------------------------------------------------------------------- SSD
SSD_CASES = [
    (2, 64, 2, 16, 16, 16),
    (1, 100, 4, 32, 16, 32),    # ragged: S % chunk != 0
    (2, 128, 2, 64, 128, 64),
]


@pytest.mark.parametrize("B,S,H,P,N,Q", SSD_CASES)
def test_ssd_matches_sequential_ref(B, S, H, P, N, Q):
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    B_ = jax.random.normal(ks[3], (B, S, N)) / np.sqrt(N)
    C_ = jax.random.normal(ks[4], (B, S, N)) / np.sqrt(N)
    D = jnp.ones((H,))
    y_k, h_k = ssd(x, dt, A, B_, C_, D, chunk=Q, interpret=True)
    y_r, h_r = ssd_ref(x, dt, A, B_, C_, D)
    np.testing.assert_allclose(y_k, y_r, atol=5e-4, rtol=1e-3)
    np.testing.assert_allclose(h_k, h_r, atol=5e-4, rtol=1e-3)


# deterministic draws from the former hypothesis domains:
# s in [3,8], q in {8,16}, n in {8,16}
SSD_SWEEP = [
    (3, 8, 8),
    (4, 16, 8),
    (5, 8, 16),
    (6, 16, 16),
    (7, 16, 8),
    (8, 8, 16),
]


@pytest.mark.parametrize("s,q,n", SSD_SWEEP)
def test_ssd_param_sweep(s, q, n):
    B, S, H, P = 1, s * 8, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(s + q), 5)
    x = jax.random.normal(ks[0], (B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    B_ = jax.random.normal(ks[3], (B, S, n)) / np.sqrt(n)
    C_ = jax.random.normal(ks[4], (B, S, n)) / np.sqrt(n)
    y_k, h_k = ssd(x, dt, A, B_, C_, chunk=q, interpret=True)
    y_r, h_r = ssd_ref(x, dt, A, B_, C_)
    np.testing.assert_allclose(y_k, y_r, atol=5e-4, rtol=1e-3)
    np.testing.assert_allclose(h_k, h_r, atol=5e-4, rtol=1e-3)


def test_model_pallas_backend_matches_xla():
    """The model's attention via the Pallas kernel (interpret) == XLA path."""
    from repro.models import ModelConfig, forward, init_params

    cfg_x = ModelConfig(name="t", family="dense", n_layers=2, d_model=64,
                        n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=128,
                        dtype="float32", q_chunk=16, attn_backend="xla")
    cfg_p = cfg_x.replace(attn_backend="pallas_interpret")
    params = init_params(jax.random.PRNGKey(0), cfg_x)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 128)
    pos = jnp.broadcast_to(jnp.arange(32), (2, 32))
    lx, _ = forward(params, toks, pos, cfg_x, mode="score")
    lp, _ = forward(params, toks, pos, cfg_p, mode="score")
    np.testing.assert_allclose(lx, lp, atol=1e-4, rtol=1e-4)


# ----------------------------------------------------- quantized KV pools
# Parity sweep across the kv_dtype ladder × block sizes × window/softcap.
# Two-sided contract: (1) kernel vs the SAME-precision oracle stays at the
# unquantized tolerance (both dequantize identical stored values, so the
# pool dtype must not perturb the kernel's arithmetic); (2) quantized
# output vs the f32-pool truth sits within an EXPLICIT tolerance ladder —
# the accuracy budget README documents per dtype.
QUANT_LADDER = {
    "float32": 2e-5,          # storage == compute: exact
    "bfloat16": 2e-2,         # 8-bit mantissa on K/V values
    "int8": 8e-2,             # symmetric absmax, per-(slot, kv-head) scale
    "fp8_e4m3": 2.5e-1,       # 3-bit mantissa, same scale granularity
}

QUANT_CASES = [
    # (bs, nb, reqs=((ctx, chunk), ...), window, softcap)
    (8, 4, ((25, 5), (9, 1)), None, None),
    (16, 3, ((33, 33), (40, 1), (17, 1)), None, 30.0),
    (32, 2, ((50, 11), (33, 1)), 12, None),
    (64, 2, ((100, 4), (90, 1)), 20, 50.0),
    # rows of P + 1 and P blocks of 16 (P = 8), a window past block 0
    (16, 10, ((140, 3), (128, 1)), 40, None),
]


def _quantize_pool(kp, vp, kv_dtype):
    """Pool leaves at the target storage dtype (+ scales when quantized)."""
    from repro.kernels.decode_attention.quant import quantize_kv
    if kv_dtype in ("float32", "bfloat16"):
        dt = jnp.dtype(kv_dtype)
        return kp.astype(dt), vp.astype(dt), None, None
    kq, ks = quantize_kv(kp, kv_dtype)
    vq, vs = quantize_kv(vp, kv_dtype)
    return kq, vq, ks, vs


@pytest.mark.parametrize("kv_dtype", ["float32", "bfloat16", "int8",
                                      "fp8_e4m3"])
@pytest.mark.parametrize("bs,nb,reqs,win,cap", QUANT_CASES)
def test_ragged_quantized_pool_parity(kv_dtype, bs, nb, reqs, win, cap):
    from repro.kernels.decode_attention.ops import (
        ragged_paged_attention_quant_ref)
    H, K, D = 4, 2, 64
    rng = np.random.default_rng(bs + len(reqs))
    ctxs = [c for c, _ in reqs]
    N = 1 + sum(-(-c // bs) for c in ctxs) + 2
    ks_ = jax.random.split(jax.random.PRNGKey(bs), 3)
    T = sum(ch for _, ch in reqs) + 2                   # 2 pad lanes
    q = jax.random.normal(ks_[0], (T, H, D), jnp.float32)
    kp = jax.random.normal(ks_[1], (N, bs, K, D), jnp.float32)
    vp = jax.random.normal(ks_[2], (N, bs, K, D), jnp.float32)
    bt = jnp.asarray(_random_block_tables(rng, N, bs, nb, ctxs))
    rows = np.full(T, -1, np.int32)
    tpos = np.full(T, -1, np.int32)
    n = 0
    for r, (ctx, chunk) in enumerate(reqs):
        rows[n:n + chunk] = r
        tpos[n:n + chunk] = np.arange(ctx - chunk, ctx)
        n += chunk
    rows, tpos = jnp.asarray(rows), jnp.asarray(tpos)

    kq, vq, kscale, vscale = _quantize_pool(kp, vp, kv_dtype)
    out = ragged_paged_attention(q, kq, vq, bt, rows, tpos, k_scale=kscale,
                                 v_scale=vscale, window=win, softcap=cap,
                                 interpret=True)
    if kscale is None:
        oracle = ragged_paged_attention_ref(q, kq, vq, bt, rows, tpos,
                                            window=win, softcap=cap)
    else:
        oracle = ragged_paged_attention_quant_ref(
            q, kq, vq, kscale, vscale, bt, rows, tpos, window=win,
            softcap=cap)
    # (1) kernel vs same-precision oracle: the unquantized tolerance
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               atol=2e-5, rtol=2e-5)
    # (2) quantized result vs the f32-pool truth: the documented ladder
    truth = ragged_paged_attention_ref(q, kp, vp, bt, rows, tpos,
                                       window=win, softcap=cap)
    tol = QUANT_LADDER[kv_dtype]
    np.testing.assert_allclose(np.asarray(out)[:n], np.asarray(truth)[:n],
                               atol=tol, rtol=tol)
    # (3) pad lanes are exact zeros at EVERY precision
    assert np.all(np.asarray(out)[n:] == 0)


@pytest.mark.parametrize("kv_dtype", ["int8", "fp8_e4m3"])
def test_paged_decode_quantized_parity(kv_dtype):
    """Single-token decode (the batched engine path) with a quantized pool."""
    from repro.kernels.decode_attention.ops import (
        paged_decode_attention_quant_ref)
    B, H, K, D, bs, nb = 3, 4, 2, 64, 16, 8
    ctxs = (100, 17, 64)
    rng = np.random.default_rng(5)
    N = 1 + sum(-(-c // bs) for c in ctxs) + 2
    ks_ = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks_[0], (B, H, D), jnp.float32)
    kp = jax.random.normal(ks_[1], (N, bs, K, D), jnp.float32)
    vp = jax.random.normal(ks_[2], (N, bs, K, D), jnp.float32)
    bt = jnp.asarray(_random_block_tables(rng, N, bs, nb, ctxs))
    qpos = jnp.asarray([c - 1 for c in ctxs], jnp.int32)
    kq, vq, kscale, vscale = _quantize_pool(kp, vp, kv_dtype)
    out = paged_decode_attention(q, kq, vq, bt, qpos, k_scale=kscale,
                                 v_scale=vscale, interpret=True)
    oracle = paged_decode_attention_quant_ref(q, kq, vq, kscale, vscale,
                                              bt, qpos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               atol=2e-5, rtol=2e-5)
    truth = paged_decode_attention_ref(q, kp, vp, bt, qpos)
    tol = QUANT_LADDER[kv_dtype]
    np.testing.assert_allclose(np.asarray(out), np.asarray(truth),
                               atol=tol, rtol=tol)


def test_quantize_kv_roundtrip_properties():
    """Unit contract of the quantizer: zero vectors round-trip to exact
    zeros with scale 1 (untouched pool blocks stay null), dequantized
    error is bounded by half a quantization step per element, and
    quantization is deterministic (same input → same bits)."""
    from repro.kernels.decode_attention.quant import (dequantize_kv,
                                                      quantize_kv)
    x = np.random.default_rng(0).normal(size=(5, 8, 2, 64)).astype(np.float32)
    x[2] = 0.0                                     # an all-zero block
    for name, step in (("int8", 1 / 127.0), ("fp8_e4m3", 1 / 8.0)):
        q, s = quantize_kv(jnp.asarray(x), name)
        q2, s2 = quantize_kv(jnp.asarray(x), name)
        assert np.array_equal(np.asarray(q), np.asarray(q2))
        assert np.array_equal(np.asarray(s), np.asarray(s2))
        assert np.all(np.asarray(s)[2] == 1.0)
        y = np.asarray(dequantize_kv(q, s))
        assert np.all(y[2] == 0.0)
        # |x - dq| <= (quant step) * amax per (token, head) row
        amax = np.abs(x).max(axis=-1, keepdims=True)
        assert np.all(np.abs(x - y) <= step * amax + 1e-7)


def test_ragged_early_out_padding_invariance():
    """Each token's trip bound must be EXACT: widening the block tables
    with extra -1 columns (a tail no token walks) and mixing rows of very
    different lengths must be bit-identical to the tight layout."""
    H, K, D, bs = 4, 2, 64, 8
    ctxs = (60, 3, 17)
    rng = np.random.default_rng(3)
    nb = max(-(-c // bs) for c in ctxs)
    N = 1 + sum(-(-c // bs) for c in ctxs) + 2
    ks_ = jax.random.split(jax.random.PRNGKey(4), 3)
    T = 5
    q = jax.random.normal(ks_[0], (T, H, D), jnp.float32)
    kp = jax.random.normal(ks_[1], (N, bs, K, D), jnp.float32)
    vp = jax.random.normal(ks_[2], (N, bs, K, D), jnp.float32)
    bt = _random_block_tables(rng, N, bs, nb, ctxs)
    rows = jnp.asarray([0, 1, 2, 2, -1], jnp.int32)
    tpos = jnp.asarray([59, 2, 15, 16, -1], jnp.int32)
    tight = ragged_paged_attention(q, kp, vp, jnp.asarray(bt), rows, tpos,
                                   interpret=True)
    wide = np.concatenate([bt, np.full((len(ctxs), 5), -1, np.int32)],
                          axis=1)
    padded = ragged_paged_attention(q, kp, vp, jnp.asarray(wide), rows,
                                    tpos, interpret=True)
    assert np.array_equal(np.asarray(tight), np.asarray(padded))
    # and the tight layout itself still matches the oracle
    ref = ragged_paged_attention_ref(q, kp, vp, jnp.asarray(bt), rows, tpos)
    np.testing.assert_allclose(np.asarray(tight), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_suggest_block_size_monotone():
    """Tuning hook sanity: bigger VMEM budgets never suggest smaller
    blocks, and the suggestion always fits the budget it was given."""
    from repro.kernels.decode_attention.kernel import suggest_block_size
    prev = 0
    for budget in (1 << 14, 1 << 16, 1 << 20, 1 << 24):
        bs = suggest_block_size(128, 8, vmem_budget_bytes=budget)
        assert bs >= prev
        prev = bs
    assert suggest_block_size(128, 8, vmem_budget_bytes=1 << 24) == 512


def test_pages_per_group_covers_128_tokens_within_its_budget():
    """A copy group holds at least 128 KV tokens where its double-buffered
    pages fit the VMEM budget, fewer where they would not, and never none;
    head_dim counts as its 128-lane padding."""
    from repro.kernels.decode_attention.kernel import (GROUP_VMEM_BYTES,
                                                       pages_per_group)
    assert pages_per_group(16, 8, 80, 2, False) == 8
    assert pages_per_group(16, 8, 120, 2, False) == 8
    assert pages_per_group(16, 2, 80, 1, True) == 8
    assert pages_per_group(128, 8, 128, 2, False) == 1
    assert pages_per_group(8, 1, 64, 4, False) == 16
    assert pages_per_group(16, 8, 80, 2, False) == pages_per_group(
        16, 8, 128, 2, False)
    big = pages_per_group(16, 16, 512, 4, True)
    assert 1 < big < 8
    assert 2 * 2 * 16 * 16 * (512 * 4 + 512) * big <= GROUP_VMEM_BYTES
    assert pages_per_group(16, 64, 1024, 4, True) == 1
