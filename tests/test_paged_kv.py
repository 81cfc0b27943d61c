"""Paged KV cache + trie prefix reuse: allocator bookkeeping (refcounts, LRU
eviction, COW-by-alignment), block-budget admission, and the serving engine
on the paged fast path (warm sessions skip prefix prefill; one device→host
sync per tick still holds; paged == dense token streams)."""
import jax
import numpy as np
import pytest

from repro.models import ModelConfig, init_params, supports_paged
from repro.serving.engine import ServeEngine
from repro.serving.kvcache import PagedCacheManager, PrefixBlockAllocator
from repro.serving.scheduler import Request, Scheduler

CFG = ModelConfig(name="t", family="dense", n_layers=2, d_model=32, n_heads=4,
                  n_kv_heads=2, d_ff=64, vocab_size=128, dtype="float32",
                  q_chunk=16)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def _toks(rng, n):
    return rng.integers(0, CFG.vocab_size, (n,)).astype(np.int32)


# ===================================================== allocator bookkeeping
def test_allocator_match_then_reuse_refcounts():
    a = PrefixBlockAllocator(num_blocks=8, block_size=4)
    toks = list(range(12))                        # 3 full blocks
    table = a.allocate(3)
    assert a.cache_blocks(toks, table) == 3
    a.unref(table)                                # request done; blocks cached
    assert a.available() == 7                     # all reclaimable, none free
    assert len(a.free) == 4
    # a new prompt sharing 2 blocks then diverging matches exactly 2
    toks2 = list(range(8)) + [99, 98, 97, 96]
    m = a.match(toks2, max_blocks=3)
    assert m == table[:2]
    assert a.refcount[m[0]] == 1 and a.refcount[m[1]] == 1
    a.unref(m)
    assert a.refcount[m[0]] == 0


def test_allocator_block_aligned_reuse_never_writes_shared():
    """COW degenerates to refcounting: reuse is capped below the full prompt,
    so the suffix (>=1 token) always lands in fresh private blocks."""
    a = PrefixBlockAllocator(num_blocks=8, block_size=4)
    toks = list(range(8))                         # exactly 2 full blocks
    t1 = a.allocate(2)
    a.cache_blocks(toks, t1)
    a.unref(t1)
    # same prompt again: at most (S-1)//bs = 1 block may be reused — the
    # last block is recomputed so last-token logits exist
    m = a.match(toks, max_blocks=(len(toks) - 1) // 4)
    assert m == t1[:1]
    a.unref(m)


def test_allocator_lru_eviction_order_and_child_pinning():
    a = PrefixBlockAllocator(num_blocks=4, block_size=2)   # 3 usable blocks
    t1 = a.allocate(2)
    a.cache_blocks([1, 2, 3, 4], t1)              # chain: parent + child
    a.unref(t1)
    t2 = a.allocate(1)
    a.cache_blocks([9, 9], t2)
    a.unref(t2)
    assert a.n_cached == 3 and len(a.free) == 0
    # the [1,2] parent is the globally-oldest entry but is PINNED by its
    # cached child, so eviction takes the child (oldest unpinned), not [9,9]
    t3 = a.allocate(1)
    assert t3 is not None and a.evictions == 1
    assert set(m.key for m in a._cached.values()) == {"/1-2", "/9-9"}
    # now the parent is unpinned and older than [9,9] → evicted next
    t4 = a.allocate(1)
    assert t4 is not None and a.evictions == 2
    assert [m.key for m in a._cached.values()] == ["/9-9"]
    a.unref(t3 + t4)
    assert a.match([9, 9, 5, 5], max_blocks=1) != []


def test_allocator_exhaustion_returns_none():
    a = PrefixBlockAllocator(num_blocks=4, block_size=2)
    t = a.allocate(3)
    assert t is not None
    assert a.allocate(1) is None                  # all blocks referenced
    a.unref(t)
    assert a.allocate(1) is not None


def test_manager_rejects_prompt_longer_than_max_len():
    """An oversized prompt must fail fast with a clear error (not overflow
    the fixed-width block table mid-admission) and leak nothing."""
    cm = PagedCacheManager(CFG, n_slots=1, max_len=16, block_size=8,
                           num_blocks=12)
    slot = cm.acquire("r1")
    with pytest.raises(ValueError, match="max_len"):
        cm.begin(slot, np.arange(24, dtype=np.int32), max_new_tokens=4)
    assert cm.n_active == 0 and cm.blocks_in_use == 0
    assert cm.block_tables().shape == (1, 2)


def test_manager_reserves_decode_growth():
    cm = PagedCacheManager(CFG, n_slots=2, max_len=32, block_size=8,
                           num_blocks=9)          # 8 usable
    slot = cm.acquire("r1")
    seq = cm.begin(slot, np.arange(8, dtype=np.int32), max_new_tokens=17)
    assert seq is not None and len(seq.table) == 1
    # 8 prompt + 16 written decode tokens → reserve 3 blocks, 2 outstanding
    assert seq.reserve == 3
    assert cm.available_for_admission() == 8 - 1 - 2


# ====================================================== scheduler admission
def test_scheduler_block_budget_is_head_of_line():
    s = Scheduler(n_replicas=1, prefill_budget=8)
    for i, n in enumerate((4, 1, 1)):
        s.submit(Request(request_id=f"r{i}", session_key="s", prompt=None,
                         max_new_tokens=n))
    cost = lambda r: {"r0": 4, "r1": 1, "r2": 1}[r.request_id]
    # r0 does not fit; r1/r2 must NOT leapfrog it (FIFO sessions stay ordered)
    assert s.admit_one(0, free_slots=3, free_blocks=3, block_cost=cost) is None
    assert s.pending(0) == 3
    # the engine loop re-reads the block budget between admissions
    got = []
    for free in (5, 1, 0):
        r = s.admit_one(0, free_slots=3, free_blocks=free, block_cost=cost)
        if r is not None:
            got.append(r.request_id)
    assert got == ["r0", "r1"] and s.pending(0) == 1


# ========================================================== engine fast path
def _run(params, reqs, **kw):
    eng = ServeEngine(CFG, params, n_slots=4, max_len=96, **kw)
    done = []
    eng.on_complete = done.append
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained()
    return eng, {r.request_id: list(r.tokens) for r in done}


def test_paged_engine_matches_dense_tokens(params):
    rng = np.random.default_rng(0)
    prompts = [_toks(rng, L) for L in (5, 40, 17, 40, 3)]
    mk = lambda: [Request(request_id=f"r{i}", session_key="s", prompt=p,
                          max_new_tokens=4) for i, p in enumerate(prompts)]
    _, dense = _run(params, mk(), paged=False)
    eng, paged = _run(params, mk(), paged=True, block_size=16)
    assert dense == paged
    # THE unified-tick invariant: one mixed dispatch, one sync, per tick
    assert eng.stats.host_syncs == eng.stats.ticks


def test_warm_session_skips_prefix_prefill(params):
    """The acceptance check: a warm multi-turn session reuses its prefix —
    prefix_hit_tokens > 0 and strictly fewer tokens are prefilled than the
    prompt carries (skipped-block count × block size) — while the
    one-sync-per-tick rule still holds and outputs match a cold engine."""
    rng = np.random.default_rng(1)
    eng = ServeEngine(CFG, params, n_slots=4, max_len=96, paged=True,
                      block_size=16)
    done = []
    eng.on_complete = done.append
    p1 = _toks(rng, 40)
    eng.submit(Request(request_id="t1", session_key="s", prompt=p1,
                       max_new_tokens=4))
    eng.run_until_drained()
    assert eng.stats.prefix_hit_tokens == 0            # cold
    # turn 2: the session's history (prompt + all generated tokens) plus new
    # user tokens — exactly what FIFO affinity delivers back to this replica
    p2 = np.concatenate([p1, np.asarray(done[0].tokens, np.int32),
                         _toks(rng, 7)])
    eng.submit(Request(request_id="t2", session_key="s", prompt=p2,
                       max_new_tokens=4))
    eng.run_until_drained()
    # turn 1 wrote KV for 40 + 3 tokens → 2 full blocks of 16 are cached
    assert eng.stats.prefix_hit_tokens == 32
    assert eng.stats.prefix_hits == 1
    skipped_blocks = eng.stats.prefix_hit_tokens // 16
    assert skipped_blocks == 2
    # strictly fewer prefill FLOPs: prefilled tokens < prompt tokens
    assert eng.stats.prefill_tokens == eng.stats.prompt_tokens - 32
    assert eng.stats.host_syncs == eng.stats.ticks
    assert eng.stats.blocks_in_use > 0
    # reused-prefix decode must equal a cold full recompute
    _, cold = _run(params, [Request(request_id="t2", session_key="s",
                                    prompt=p2, max_new_tokens=4)], paged=False)
    assert cold["t2"] == done[1].tokens


def test_paged_decode_via_pallas_kernel_matches_xla(params):
    """The block-gather Pallas kernel wired through the model: same tokens
    as the XLA gather path."""
    rng = np.random.default_rng(2)
    p = _toks(rng, 20)
    mk = lambda: [Request(request_id="k", session_key="s", prompt=p,
                          max_new_tokens=3)]
    _, xla = _run(params, mk(), paged=True, block_size=16)
    cfg_p = CFG.replace(attn_backend="pallas_interpret")
    eng = ServeEngine(cfg_p, params, n_slots=4, max_len=96, paged=True,
                      block_size=16)
    done = []
    eng.on_complete = done.append
    eng.submit(mk()[0])
    eng.run_until_drained()
    assert list(done[0].tokens) == xla["k"]


def test_prefix_cache_eviction_under_pressure(params):
    """A tiny pool: old sessions' cached blocks are evicted LRU-first and
    serving keeps going (admission never overruns the pool)."""
    rng = np.random.default_rng(3)
    eng = ServeEngine(CFG, params, n_slots=2, max_len=64, paged=True,
                      block_size=16, num_blocks=9)     # 8 usable blocks
    for i in range(6):
        eng.submit(Request(request_id=f"r{i}", session_key=f"s{i}",
                           prompt=_toks(rng, 33), max_new_tokens=2))
    eng.run_until_drained()
    assert eng.stats.prefills == 6
    assert eng.cm.alloc.evictions > 0
    assert eng.cm.n_active == 0
    assert eng.stats.host_syncs == eng.stats.ticks


# ============================================ review regressions (PR 2 fixes)
def test_allocator_commit_dedup_swaps_duplicates():
    """Two tables caching the same not-yet-cached prefix: the second commit
    must adopt the incumbent blocks (table rewritten in place) and free its
    duplicates, so available() only counts truly reclaimable blocks."""
    a = PrefixBlockAllocator(num_blocks=8, block_size=4)
    shared = [1, 2, 3, 4, 5, 6, 7, 8]             # 2 full blocks
    ta = a.allocate(3)
    tb = a.allocate(3)
    a.cache_blocks(shared + [10, 11, 12, 13], ta)
    dup = list(tb)
    assert a.cache_blocks(shared + [20, 21, 22, 23], tb) == 1  # divergent only
    assert tb[:2] == ta[:2] and a.dedup_blocks == 2
    assert a.refcount[ta[0]] == 2 and a.refcount[ta[1]] == 2
    assert a.refcount[dup[0]] == 0 and dup[0] in a.free and dup[1] in a.free
    a.unref(ta)
    a.unref(tb)
    assert a.available() == 7
    got = a.allocate(7)                           # every counted block is
    assert got is not None and len(set(got)) == 7  # actually obtainable


def test_same_tick_divergent_prefix_never_strands_blocks(params):
    """High-severity regression: A and B admitted in ONE tick share two
    blocks of prompt then diverge in their third; A finishes while B keeps
    decoding, and C then needs every block available() advertises.  With the
    unified tick's chunk-granularity trie commit, B matches A's same-tick
    committed blocks at admission (intra-batch sharing — no duplicate
    prefill, no dedup needed) and the allocator's accounting stays exact."""
    rng = np.random.default_rng(5)
    eng = ServeEngine(CFG, params, n_slots=4, max_len=32, paged=True,
                      block_size=4, num_blocks=11)      # 10 usable blocks
    done = []
    eng.on_complete = done.append
    shared = _toks(rng, 8)
    mk = lambda rid, tail, n: Request(
        request_id=rid, session_key=rid,
        prompt=np.concatenate([shared, tail]), max_new_tokens=n)
    eng.submit(mk("a", _toks(rng, 4), 2))               # cost 4 blocks
    eng.submit(mk("b", _toks(rng, 4), 6))               # cost 5 blocks
    eng.tick()                             # ONE mixed dispatch prefills both
    # intra-batch sharing: B reused A's 2 shared blocks (committed when A's
    # chunk was packed, read in the same dispatch) instead of duplicating
    assert eng.stats.prefix_hit_tokens == 8 and eng.stats.prefix_hits == 1
    assert eng.stats.prefill_tokens == 12 + 4
    assert eng.cm.alloc.dedup_blocks == 0               # nothing to reconcile
    assert eng.cm.n_active == 2                # both live after first token
    eng.tick()                                          # A's 2nd token: done
    assert [r.request_id for r in done] == ["a"] and eng.cm.n_active == 1
    eng.submit(Request(request_id="c", session_key="c",
                       prompt=_toks(rng, 20), max_new_tokens=1))  # cost 5
    eng.run_until_drained()
    assert sorted(r.request_id for r in done) == ["a", "b", "c"]
    assert all(r.error is None for r in done)
    a = eng.cm.alloc
    assert a.available() == a.num_blocks - 1
    got = a.allocate(a.num_blocks - 1)       # drain: all blocks reclaimable
    assert got is not None and len(set(got)) == a.num_blocks - 1


def test_oversized_prompt_fails_via_completion_path(params):
    """Medium regression: an oversized prompt mid-batch must fail ALONE
    through the completion path (error set, no tokens) without stranding
    the same-tick requests admitted before it."""
    rng = np.random.default_rng(6)
    eng = ServeEngine(CFG, params, n_slots=4, max_len=32, paged=True,
                      block_size=16)
    done = []
    eng.on_complete = done.append
    for rid, n, new in (("g1", 8, 2), ("bad", 40, 2), ("g2", 8, 2),
                        ("over", 30, 5)):       # 30 + 4 written > max_len=32
        eng.submit(Request(request_id=rid, session_key=rid,
                           prompt=_toks(rng, n), max_new_tokens=new))
    eng.run_until_drained()
    byid = {r.request_id: r for r in done}
    assert set(byid) == {"g1", "bad", "g2", "over"}
    assert byid["bad"].error is not None and "max_len" in byid["bad"].error
    assert byid["bad"].tokens == []
    # a prompt that fits but whose DECODE would overrun max_len must also be
    # rejected up front — mid-decode it would crash the whole replica tick
    assert byid["over"].error is not None and "max_len" in byid["over"].error
    for rid in ("g1", "g2"):
        assert byid[rid].error is None and len(byid[rid].tokens) == 2
    assert eng.cm.n_active == 0


def test_impossible_block_demand_rejected_not_stalled(params):
    """Scheduler regression: a request whose worst-case block demand exceeds
    what the pool can EVER provide is rejected with an explicit error at
    submit instead of parking at the head of the queue forever."""
    rng = np.random.default_rng(7)
    eng = ServeEngine(CFG, params, n_slots=2, max_len=96, paged=True,
                      block_size=16, num_blocks=5)      # 4 usable blocks
    done = []
    eng.on_complete = done.append
    eng.submit(Request(request_id="big", session_key="s",
                       prompt=_toks(rng, 70), max_new_tokens=20))  # needs 6
    # the harder path: enqueued straight into the scheduler (bypassing
    # engine.submit's up-front check) — admit() must pop it through to the
    # engine's admission-time rejection instead of parking it forever
    eng.scheduler.submit(Request(request_id="big2", session_key="s",
                                 prompt=_toks(rng, 70), max_new_tokens=20))
    eng.submit(Request(request_id="ok", session_key="s",
                       prompt=_toks(rng, 8), max_new_tokens=2))
    eng.run_until_drained()                   # would TimeoutError when stalled
    byid = {r.request_id: r for r in done}
    for rid in ("big", "big2"):
        assert byid[rid].error is not None and "KV blocks" in byid[rid].error
    assert byid["ok"].error is None and len(byid["ok"].tokens) == 2


def test_begin_failure_requeues_in_order(params, monkeypatch):
    """Engine regression: a begin() refusal (accounting drift) requeues the
    request and everything admitted after it — order preserved — instead of
    crashing the tick on an assert."""
    rng = np.random.default_rng(8)
    eng = ServeEngine(CFG, params, n_slots=4, max_len=64, paged=True,
                      block_size=16)
    real = eng.cm.begin
    calls = {"n": 0}

    def flaky(slot, prompt, max_new):
        calls["n"] += 1
        if calls["n"] == 1:
            eng.cm.release(slot)
            return None
        return real(slot, prompt, max_new)

    monkeypatch.setattr(eng.cm, "begin", flaky)
    done = []
    eng.on_complete = done.append
    for rid in ("r1", "r2"):
        eng.submit(Request(request_id=rid, session_key="s",
                           prompt=_toks(rng, 8), max_new_tokens=2))
    eng.run_until_drained()
    assert [r.request_id for r in done] == ["r1", "r2"]
    assert calls["n"] == 3 and eng.cm.n_active == 0


def test_decode_donates_pool_buffers(params):
    """Perf regression: the jitted paged steps donate the pool operand (no
    whole-pool copy per tick); the devstore entry always holds the live
    leaves after publish()."""
    rng = np.random.default_rng(9)
    eng = ServeEngine(CFG, params, n_slots=2, max_len=32, paged=True,
                      block_size=16)
    before = jax.tree.leaves(eng.cm.pools)
    eng.submit(Request(request_id="r", session_key="s", prompt=_toks(rng, 5),
                       max_new_tokens=3))
    eng.run_until_drained()
    assert all(leaf.is_deleted() for leaf in before)
    stored = eng.cm.devstore.get(eng.cm.kv_key)
    assert all(a is b for a, b in zip(jax.tree.leaves(stored),
                                      jax.tree.leaves(eng.cm.pools)))


# =============================================== speculative KV rollback
def test_rollback_accounting_matches_accepted_only_replay():
    """Speculative rollback invariant (seeded deterministic sweep): after
    ANY accept/reject pattern — random draft lengths, random accepted
    prefixes, across a request's whole lifetime — the allocator's state
    (blocks in use, free-list size, trie residency, refcount multiset,
    available()) equals a from-scratch replay that only ever wrote the
    accepted tokens; and rejected-tail blocks are freed exactly once (the
    free list never holds a duplicate)."""
    for seed in range(8):
        rng = np.random.default_rng(100 + seed)
        bs = 4
        mk = lambda: PagedCacheManager(CFG, n_slots=1, max_len=64,
                                       block_size=bs, num_blocks=24)
        cm, cm2 = mk(), mk()
        S = int(rng.integers(3, 12))
        max_new = int(rng.integers(4, 14))
        prompt = rng.integers(0, 100, (S,)).astype(np.int32)
        # --- speculative lifetime on cm: each "tick" drafts m tokens,
        # accepts a <= m, rolls the rejected tail back
        slot = cm.acquire("r")
        seq = cm.begin(slot, prompt, max_new)
        assert cm.commit_prefill_progress(slot, S)
        generated = [int(rng.integers(0, 100))]        # boundary token
        while len(generated) < max_new:
            room = max_new - len(generated) - 1
            m = int(rng.integers(0, min(4, room) + 1))
            cm.ensure_decode_blocks({slot: m})
            a = int(rng.integers(0, m + 1))            # accepted prefix
            generated += [int(rng.integers(0, 100)) for _ in range(a + 1)]
            seq.pos += a + 1
            if a < m:
                cm.rollback_writes(slot, seq.pos)
            free = cm.alloc.free
            assert len(set(free)) == len(free), "block freed twice"
        cm.finish(slot, generated)
        # --- plain replay on cm2: the same accepted stream, one token at
        # a time, no speculation
        slot2 = cm2.acquire("r")
        seq2 = cm2.begin(slot2, prompt, max_new)
        assert cm2.commit_prefill_progress(slot2, S)
        for _ in range(len(generated) - 1):
            cm2.ensure_decode_blocks()
            seq2.pos += 1
        cm2.finish(slot2, generated)
        a1, a2 = cm.alloc, cm2.alloc
        assert a1.blocks_in_use == a2.blocks_in_use
        assert len(a1.free) == len(a2.free)
        assert a1.n_cached == a2.n_cached
        assert sorted(a1.refcount) == sorted(a2.refcount)
        assert a1.available() == a2.available()
        # identical trie CONTENT (paths key on tokens, not block ids)
        assert set(a1._cached.keys()) == set(a2._cached.keys())


def test_rollback_never_touches_shared_prefix_blocks():
    """A rolled-back speculative tail must free only the request's private
    tail blocks: trie-resident shared prefix blocks keep their refcounts,
    residency, and children pins."""
    bs = 4
    cm = PagedCacheManager(CFG, n_slots=2, max_len=32, block_size=bs,
                           num_blocks=16)
    prompt = np.arange(9, dtype=np.int32)              # 2 full blocks + 1
    sa = cm.acquire("a")
    cm.begin(sa, prompt, 4)
    assert cm.commit_prefill_progress(sa, 9)           # blocks 0-1 cached
    sb = cm.acquire("b")
    seq_b = cm.begin(sb, prompt, 12)
    assert seq_b.reused == 8                           # both full blocks
    shared = list(seq_b.table[:2])
    rc_before = [cm.alloc.refcount[b] for b in shared]
    cached_before = cm.alloc.n_cached
    assert cm.commit_prefill_progress(sb, 9)
    # b speculates 5 drafts deep past its prompt, all rejected
    seq_b.pos = 9
    cm.ensure_decode_blocks({sb: 5})
    grown = len(seq_b.table)
    seq_b.pos += 1                                      # only t_last kept
    freed = cm.rollback_writes(sb, seq_b.pos)
    assert freed == grown - len(seq_b.table) and freed > 0
    assert [cm.alloc.refcount[b] for b in shared] == rc_before
    assert cm.alloc.n_cached == cached_before
    assert len(set(cm.alloc.free)) == len(cm.alloc.free)
    # the freed blocks are genuinely reusable: drain the whole pool
    cm.finish(sb, [1, 2])
    cm.release(sa)
    a = cm.alloc
    got = a.allocate(a.num_blocks - 1)
    assert got is not None and len(set(got)) == a.num_blocks - 1


def test_rollback_noop_when_everything_accepted():
    """Full acceptance leaves nothing to roll back: the table already
    covers exactly the written positions."""
    cm = PagedCacheManager(CFG, n_slots=1, max_len=32, block_size=4,
                           num_blocks=12)
    slot = cm.acquire("r")
    seq = cm.begin(slot, np.arange(5, dtype=np.int32), 10)
    assert cm.commit_prefill_progress(slot, 5)
    cm.ensure_decode_blocks({slot: 3})
    seq.pos += 4                                        # all 3 drafts + bonus
    assert cm.rollback_writes(slot, seq.pos) == 0
    assert len(seq.table) * 4 >= seq.pos


def test_supports_paged_gating():
    assert supports_paged(CFG)
    mamba = ModelConfig(name="m", family="ssm", n_layers=2, d_model=32,
                        n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=64,
                        dtype="float32")
    assert not supports_paged(mamba)
    with pytest.raises(ValueError):
        ServeEngine(mamba, None, paged=True)


def test_kv_pool_lives_on_devstore(params):
    """KV blocks are Cascade objects: the engine's pool tree is installed on
    the device store and re-installed (same leaves, no copy) every tick."""
    eng = ServeEngine(CFG, params, n_slots=2, max_len=32, paged=True,
                      block_size=16)
    stored = eng.cm.devstore.get(eng.cm.kv_key)
    assert stored is not None
    assert jax.tree.structure(stored) == jax.tree.structure(eng.cm.pools)
    rng = np.random.default_rng(4)
    eng.submit(Request(request_id="r", session_key="s", prompt=_toks(rng, 5),
                       max_new_tokens=2))
    eng.run_until_drained()
    stored = eng.cm.devstore.get(eng.cm.kv_key)
    # zero-copy install: the stored leaves ARE the live pool leaves
    assert all(a is b for a, b in zip(jax.tree.leaves(stored),
                                      jax.tree.leaves(eng.cm.pools)))


# ===================================================== quantized KV pools
def test_quantized_pool_bytes_match_roofline_accounting():
    """The manager's measured kv_bytes_per_token must equal the roofline
    theoretical formula at every precision (the int8-vs-bf16 byte-ratio
    claim is made on that formula), and the pool leaves must carry the
    advertised storage dtypes — quantized pools with f32 scale leaves."""
    import jax.numpy as jnp

    from benchmarks.roofline import kv_bytes_per_decode_token
    D = CFG.d_model // CFG.n_heads
    expect_dt = {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
                 "int8": jnp.int8, "fp8_e4m3": jnp.float8_e4m3fn}
    bytes_by_dt = {}
    for kv_dtype in ("float32", "bfloat16", "int8", "fp8_e4m3"):
        cm = PagedCacheManager(CFG, n_slots=2, max_len=32, block_size=8,
                               num_blocks=8, kv_dtype=kv_dtype)
        got = cm.kv_bytes_per_token()
        theor = kv_bytes_per_decode_token(CFG.n_layers, CFG.n_kv_heads, D,
                                          kv_dtype)
        assert got == theor, (kv_dtype, got, theor)
        bytes_by_dt[kv_dtype] = got
        dts = {l.dtype for l in jax.tree.leaves(cm.pools)}
        if kv_dtype in ("int8", "fp8_e4m3"):
            assert dts == {jnp.dtype(expect_dt[kv_dtype]),
                           jnp.dtype(jnp.float32)}
        else:
            assert dts == {jnp.dtype(expect_dt[kv_dtype])}
    assert (bytes_by_dt["float32"] > bytes_by_dt["bfloat16"]
            > bytes_by_dt["int8"] == bytes_by_dt["fp8_e4m3"])


@pytest.mark.parametrize("kv_dtype", ["int8", "fp8_e4m3"])
def test_quantized_streams_deterministic_across_backends(params, kv_dtype):
    """Fixed precision is a determinism contract: the same prompts through
    the quantized pool yield bit-identical greedy streams run-to-run AND
    across attention backends (XLA gather vs Pallas kernel — both
    dequantize the same stored integers)."""
    rng = np.random.default_rng(21)
    prompts = [_toks(rng, L) for L in (5, 40, 17)]
    mk = lambda: [Request(request_id=f"r{i}", session_key="s", prompt=p,
                          max_new_tokens=4) for i, p in enumerate(prompts)]
    eng, xla1 = _run(params, mk(), paged=True, block_size=16,
                     kv_dtype=kv_dtype)
    _, xla2 = _run(params, mk(), paged=True, block_size=16,
                   kv_dtype=kv_dtype)
    assert xla1 == xla2
    cfg_p = CFG.replace(attn_backend="pallas_interpret")
    eng_p = ServeEngine(cfg_p, params, n_slots=4, max_len=96, paged=True,
                        block_size=16, kv_dtype=kv_dtype)
    done = []
    eng_p.on_complete = done.append
    for r in mk():
        eng_p.submit(r)
    eng_p.run_until_drained()
    assert {r.request_id: list(r.tokens) for r in done} == xla1
    assert eng.stats.host_syncs == eng.stats.ticks
    assert eng_p.stats.host_syncs == eng_p.stats.ticks


def test_quantized_pool_under_bf16_model_on_both_backends():
    """An int8 pool under a bf16 model: the XLA path dequantizes to the
    model's dtype, so attention hands the layer scan the dtype it was given
    (as the kernel does) and both backends serve the same requests."""
    cfg = CFG.replace(dtype="bfloat16")
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(3)
    prompts = [_toks(rng, L) for L in (5, 40)]
    scores = []
    for backend in (None, "pallas_interpret"):
        eng = ServeEngine(cfg.replace(attn_backend=backend), params,
                          n_slots=2, max_len=96, block_size=16,
                          kv_dtype="int8")
        reqs = [Request(request_id=f"r{i}", session_key=f"s{i}", prompt=p,
                        max_new_tokens=4) for i, p in enumerate(prompts)]
        for r in reqs:
            eng.submit(r)
        eng.run_until_drained()
        assert all(len(r.tokens) == 4 and r.error is None for r in reqs)
        assert eng.stats.host_syncs == eng.stats.ticks
        scores.append([r.scores[0] for r in reqs])
    np.testing.assert_allclose(scores[0], scores[1], atol=0.05)


def test_quantized_spill_adopt_scales_bit_exact():
    """Property test on the migration path: spill_device → host → adopt on
    a sibling manager round-trips EVERY pool leaf bit-exactly — the int8
    payloads and their f32 scales travel as ordinary tree leaves, no
    requantization anywhere."""
    import jax.numpy as jnp

    from repro.serving.kvcache import SpilledKV
    rng = np.random.default_rng(7)
    src = PagedCacheManager(CFG, n_slots=2, max_len=64, block_size=8,
                            num_blocks=12, kv_dtype="int8")
    leaves, treedef = jax.tree.flatten(src.pools)
    filled = []
    for leaf in leaves:
        if leaf.dtype == jnp.int8:
            filled.append(jnp.asarray(
                rng.integers(-127, 128, leaf.shape), jnp.int8))
        else:                                   # f32 scale leaves
            assert leaf.dtype == jnp.float32
            filled.append(jnp.asarray(
                rng.uniform(0.25, 4.0, leaf.shape), jnp.float32))
    src.pools = jax.tree.unflatten(treedef, filled)
    src.publish()
    slot = src.acquire("mig")
    src.slots[slot].table = [3, 1, 5]           # table ORDER must survive
    host = jax.tree.map(np.asarray, src.spill_device(slot))
    sp = SpilledKV(request_id="mig", pos=20, n_blocks=3, block_size=8,
                   blocks=host)
    dst = PagedCacheManager(CFG, n_slots=2, max_len=64, block_size=8,
                            num_blocks=12, kv_dtype="int8")
    slot2 = dst.acquire("mig")
    seq = dst.adopt(slot2, np.arange(10, dtype=np.int32), sp,
                    max_new_tokens=4)
    assert seq is not None and seq.pos == 20
    back = jax.tree.map(np.asarray, dst.spill_device(slot2))
    h_leaves = jax.tree.leaves(host)
    b_leaves = jax.tree.leaves(back)
    assert len(h_leaves) == len(b_leaves)
    for a, b in zip(h_leaves, b_leaves):
        assert a.dtype == b.dtype
        assert np.array_equal(a, b)             # bit-exact, scales included
    assert {a.dtype for a in h_leaves} == {np.dtype(np.int8),
                                           np.dtype(np.float32)}


@pytest.mark.parametrize("kv_dtype", ["int8", "fp8_e4m3"])
def test_quantized_preempt_resume_bit_identical(params, kv_dtype):
    """Preempt → spill to host pool → re-issue → adopt, all at fixed
    quantized precision: the greedy streams must be bit-identical to the
    uninterrupted quantized run (a written token's quantized bytes depend
    only on that token, so migration never perturbs neighbours)."""
    import time

    from repro.core.store import SpillPool
    from repro.serving.scheduler import SLO_BATCH, SLO_INTERACTIVE
    rng = np.random.default_rng(13)
    prompts = {"b0": _toks(rng, 8), "b1": _toks(rng, 8), "i0": _toks(rng, 4)}
    mk = lambda rid, slo: Request(
        request_id=rid, session_key=f"sess-{rid}", prompt=prompts[rid],
        max_new_tokens=3 if slo == SLO_INTERACTIVE else 8, slo=slo)

    # uninterrupted reference at the SAME precision: slack capacity
    ref_eng = ServeEngine(CFG, params, n_slots=8, max_len=48,
                          temperature=0.0, block_size=4, num_blocks=64,
                          prefix_cache=False, kv_dtype=kv_dtype)
    ref_done = {}
    ref_eng.on_complete = lambda r: ref_done.setdefault(r.request_id, r)
    for rid in ("b0", "b1", "i0"):
        ref_eng.submit(mk(rid, SLO_INTERACTIVE if rid == "i0"
                          else SLO_BATCH))
    ref_eng.run_until_drained()
    assert ref_eng.stats.preemptions == 0
    ref = {rid: list(r.tokens) for rid, r in ref_done.items()}

    # tight engine: interactive arrival mid-decode forces a preemption
    pool = SpillPool(capacity_blocks=64)
    eng = ServeEngine(CFG, params, n_slots=2, max_len=48, temperature=0.0,
                      block_size=4, num_blocks=11, prefix_cache=False,
                      spill_pool=pool, preempt=True, kv_dtype=kv_dtype)
    done = {}
    eng.on_complete = lambda r: done.setdefault(r.request_id, r)
    eng.submit(mk("b0", SLO_BATCH))
    eng.submit(mk("b1", SLO_BATCH))
    stop = time.monotonic() + 30
    while not (len(eng.live) == 2
               and all(r.tokens for r in eng.live.values())):
        eng.tick()
        assert time.monotonic() < stop, "batch requests never went live"
    eng.submit(mk("i0", SLO_INTERACTIVE))
    eng.run_until_drained()
    got = {rid: list(r.tokens) for rid, r in done.items()}
    assert got == ref
    assert eng.stats.preemptions >= 1
    assert eng.stats.resumes >= 1               # adopted, not replayed
    assert eng.stats.host_syncs == eng.stats.ticks + eng.stats.spill_syncs
    assert pool.blocks == 0 and pool.evicted == 0


def test_quantized_decode_donates_pool_buffers(params):
    """Donation must stay exact-match with the scale leaves in the tree:
    the jitted paged step still donates the whole pool (no copy-per-tick
    fallback when the tree gains k_scale/v_scale)."""
    rng = np.random.default_rng(17)
    eng = ServeEngine(CFG, params, n_slots=2, max_len=32, paged=True,
                      block_size=16, kv_dtype="int8")
    before = jax.tree.leaves(eng.cm.pools)
    assert len({l.dtype for l in before}) == 2  # int8 payload + f32 scales
    eng.submit(Request(request_id="r", session_key="s", prompt=_toks(rng, 5),
                       max_new_tokens=3))
    eng.run_until_drained()
    assert all(leaf.is_deleted() for leaf in before)
    stored = eng.cm.devstore.get(eng.cm.kv_key)
    assert all(a is b for a, b in zip(jax.tree.leaves(stored),
                                      jax.tree.leaves(eng.cm.pools)))
