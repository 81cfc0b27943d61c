"""The serving program's own instrumentation: host spans on the profiler's
clock, device scopes in the tick's metadata, the ragged kernel's grid
counters, the per-request TPOT entry and the enqueue stamp.

The spans are read back from a real ``jax.profiler`` trace of a tiny
deployment on the CPU; the counters are checked against a tick packed by
hand, lane by lane.
"""
import glob

import jax
import numpy as np
import pytest

from repro.models import ModelConfig, init_params
from repro.serving.cluster import ServeNode
from repro.serving.engine import ServeEngine, attention_grid
from repro.serving.scheduler import Request

CFG = ModelConfig(name="t", family="dense", n_layers=2, d_model=32, n_heads=4,
                  n_kv_heads=2, d_ff=64, vocab_size=128, dtype="float32",
                  q_chunk=16)
TICK = ("cascade.tick.pack", "cascade.tick.dispatch", "cascade.tick.sync",
        "cascade.tick.emit")


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def _req(rid, n, max_new=4, seed=0):
    rng = np.random.default_rng(seed)
    return Request(request_id=rid, session_key=rid, max_new_tokens=max_new,
                   prompt=rng.integers(0, CFG.vocab_size, (n,)).astype(
                       np.int32))


def _spans(trace_dir) -> list:
    """(name, start_ns, end_ns, args, thread line) of every ``cascade.*``
    host event in a trace directory."""
    path = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")[0]
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("cascade."):
                    out.append((e.name, int(e.start_ns),
                                int(e.start_ns + e.duration_ns),
                                dict(e.stats), (plane.name, i)))
    return out


def test_each_tick_phase_is_one_span_per_tick(params, tmp_path):
    with ServeNode(n_workers=1) as node:
        dep = node.deploy("m", CFG, params, n_replicas=1, n_slots=4,
                          max_len=64)
        dep.submit("warm", "w", np.arange(5, dtype=np.int32),
                   max_new_tokens=2)
        node.run_until_drained()             # compiles outside the trace
        jax.profiler.start_trace(str(tmp_path))
        try:
            for i in range(5):
                dep.submit(f"s{i}", f"r{i}",
                           np.arange(3 + 4 * i, dtype=np.int32) % 97,
                           max_new_tokens=3 + i)
            before = dep.stats()
            node.run_until_drained()
            after = dep.stats()
        finally:
            jax.profiler.stop_trace()
    spans = _spans(tmp_path)
    names = [s[0] for s in spans]
    ticks = after["ticks"] - before["ticks"]
    assert ticks > 0 and after["host_syncs"] == after["ticks"]
    for name in TICK:
        assert names.count(name) == ticks, name
    assert names.count("cascade.complete") == 5
    assert names.count("cascade.upcall") == 5
    assert names.count("cascade.node.health") >= ticks
    # one thread packs, dispatches, pulls and emits, in that order, and a
    # completion runs inside the emit that finished its request
    phases = sorted((s for s in spans if s[0] in TICK), key=lambda s: s[1])
    assert len({s[4] for s in phases}) == 1
    assert [s[0] for s in phases] == list(TICK) * ticks
    assert all(a[2] <= b[1] for a, b in zip(phases, phases[1:]))
    emits = [s for s in phases if s[0] == "cascade.tick.emit"]
    for c in (s for s in spans if s[0] == "cascade.complete"):
        assert any(e[1] <= c[1] and c[2] <= e[2] for e in emits)
    # the dispatch span carries the tick's attention-grid counts
    got = {k: sum(s[3][k] for s in phases if s[0] == "cascade.tick.dispatch")
           for k in ("attn_grid_blocks", "attn_live_blocks",
                     "attn_pad_blocks")}
    assert got == {k: after[k] - before[k] for k in got}


def test_attention_grid_counts_a_hand_packed_table():
    """Blocks of 4, copy groups of 2 pages.  Row 0 has 3 live blocks, row
    1 one, row 2 none; row 0's lanes sit at positions 5 and 10, row 1's
    at 0-2, row 2's at 0, and two pad lanes carry no position."""
    bt = np.array([[5, 6, 7, -1], [8, -1, -1, -1], [-1, -1, -1, -1]],
                  np.int32)
    rows = np.array([0, 0, 1, 1, 1, -1, -1, 2], np.int32)
    pos = np.array([5, 10, 0, 1, 2, -1, -1, 0], np.int32)
    # causal: position 5 walks 2 blocks, 10 walks 3; row 1's lanes 1 each;
    # pads and the empty row nothing; groups round 2, 3, 1, 1, 1 up to 2
    assert attention_grid(bt, rows, pos, block_size=4, pages=2) == (
        2 + 4 + 2 + 2 + 2, 2 + 3 + 1 + 1 + 1, 0)
    # a window of 6 starts position 10 at block (10 - 6 + 1) // 4 = 1
    assert attention_grid(bt, rows, pos, block_size=4, pages=2,
                          window=6) == (2 + 2 + 2 + 2 + 2, 2 + 2 + 1 + 1 + 1,
                                        0)


def test_a_pad_lane_walks_nothing():
    """A lane with no row walks nothing even where it carries a position,
    and so does a lane at position -1 on a live row: the counters count
    no block for either, and the kernel writes exact zeros there."""
    from repro.kernels.decode_attention.ops import ragged_paged_attention

    bt = np.array([[3, 1, 2, -1]], np.int32)
    rows = np.array([0, -1, 0, -1], np.int32)
    pos = np.array([9, 9, -1, -1], np.int32)
    assert attention_grid(bt, rows, pos, block_size=4, pages=2) == (4, 3, 0)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (4, 4, 8), np.float32)
    kp = jax.random.normal(ks[1], (4, 4, 2, 8), np.float32)
    vp = jax.random.normal(ks[2], (4, 4, 2, 8), np.float32)
    out = np.asarray(ragged_paged_attention(q, kp, vp, bt, rows, pos,
                                            interpret=True))
    assert np.all(out[1:] == 0) and np.all(out[0] != 0)


def test_a_tick_of_decode_rows_a_chunk_and_pads_counts_by_hand(params):
    """Blocks of 4, 8 columns, 16 lanes, 2 layers; at these shapes the
    kernel copies P = 32 pages a group, so each lane's group rounds up to
    32 slots.  Tick 1 packs A's 10-token prompt (slot 0, 3 blocks) and B's
    3 (slot 1, 1 block) with 3 pad lanes; tick 2 packs A's and B's decode
    rows and C's 9-token prompt (slot 2, 3 blocks) with 5 pads.  Each
    token walks only the blocks up to its own position; pads walk none."""
    eng = ServeEngine(CFG, params, n_slots=4, max_len=32, paged=True,
                      block_size=4, token_budget=16, prefix_cache=False)
    assert eng._attn_pages == 32 and eng._attn_windows == {None: 2}
    eng.submit(_req("A", 10, seed=1))
    eng.submit(_req("B", 3, seed=2))
    eng.tick()
    st = eng.stats
    assert [len(eng.cm.slots[s].table) for s in (0, 1)] == [3, 1]
    # A's positions 0-9 walk 1, 1, 1, 1, 2, 2, 2, 2, 3, 3 blocks; B's 0-2
    # one each
    assert (st.attn_grid_blocks, st.attn_live_blocks,
            st.attn_pad_blocks) == (2 * 13 * 32, 2 * (18 + 3), 0)
    eng.submit(_req("C", 9, seed=3))
    eng.tick()
    assert [len(eng.cm.slots[s].table) for s in (0, 1, 2)] == [3, 1, 3]
    # A decodes at position 10 (3 blocks), B at 3 (1), C's 0-8 walk 15
    assert (st.attn_grid_blocks, st.attn_live_blocks,
            st.attn_pad_blocks) == (
        2 * 13 * 32 + 2 * 11 * 32, 2 * 21 + 2 * (3 + 1 + 15), 0)
    assert st.host_syncs == st.ticks == 2


def test_tpot_is_one_entry_per_finished_request(params):
    eng = ServeEngine(CFG, params, n_slots=4, max_len=64, paged=True)
    done = []
    eng.on_complete = done.append
    for i, new in enumerate((1, 3, 6, 9)):
        eng.submit(_req(f"r{i}", 5 + i, max_new=new, seed=i))
    eng.run_until_drained()
    multi = [r for r in done if len(r.tokens) > 1]
    assert len(done) == 4 and len(multi) == 3
    assert sorted(eng.stats.tpot_s) == sorted(
        (r.done_s - r.first_token_s) / (len(r.tokens) - 1) for r in multi)
    assert eng.stats.tokens_out == 1 + 3 + 6 + 9


def test_enqueue_stamp_splits_delivery_from_the_scheduler_wait(params):
    with ServeNode(n_workers=1) as node:
        dep = node.deploy("m", CFG, params, n_replicas=1, n_slots=2,
                          max_len=64)
        got = []
        dep.on_done.append(got.append)
        for i in range(4):
            dep.submit(f"s{i}", f"r{i}", np.arange(4, dtype=np.int32) + i,
                       max_new_tokens=3)
        node.run_until_drained()
        st = dep.stats()
    assert len(got) == 4
    for r in got:
        assert r.arrived_s <= r.enqueued_s <= r.issued_s <= r.first_token_s
    for k in ("attn_grid_blocks", "attn_live_blocks"):
        assert st[k] > 0
    assert 0 == st["attn_pad_blocks"] < st["attn_live_blocks"] \
        <= st["attn_grid_blocks"]


def test_the_tick_names_its_phases_in_its_metadata(params):
    """The engine's compiled tick carries each phase's scope in its ops'
    op_name metadata (the XLA attention path here; the kernel's layout
    scope and custom-call name are checked for a described TPU in
    test_tpu_compile.py)."""
    eng = ServeEngine(CFG, params, n_slots=2, max_len=32, paged=True)
    hlo = eng.lower_tick().compile().as_text()
    for scope in ("/embed/", "/qkv/", "/kv_pool/write/", "/attention/",
                  "/mlp/", "/head/", "/sample/"):
        assert scope in hlo, scope
