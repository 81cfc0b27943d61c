"""Serving engine + sharding rules: continuous batching, FIFO sessions,
logical-axis resolution properties."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ARCH_IDS, SHAPES, get_config
from repro.core.pools import DispatchPolicy
from repro.launch.sharding import leaf_spec, make_rules, tree_shardings
from repro.models import ModelConfig, init_params, param_axes
from repro.serving.engine import ServeEngine
from repro.serving.scheduler import Request, Scheduler

CFG = ModelConfig(name="t", family="dense", n_layers=2, d_model=32, n_heads=4,
                  n_kv_heads=2, d_ff=64, vocab_size=128, dtype="float32",
                  q_chunk=16)


# ------------------------------------------------------------------ serving
def test_engine_drains_and_counts():
    params = init_params(jax.random.PRNGKey(0), CFG)
    eng = ServeEngine(CFG, params, n_slots=3, max_len=32)
    rng = np.random.default_rng(0)
    for i in range(7):  # more requests than slots → queueing + reuse
        eng.submit(Request(request_id=f"r{i}", session_key=f"s{i}",
                           prompt=rng.integers(0, 128, (5,)).astype(np.int32),
                           max_new_tokens=4))
    eng.run_until_drained()
    assert eng.stats.prefills == 7
    assert eng.stats.tokens_out == 7 * 4
    assert eng.cm.n_active == 0


def test_engine_greedy_matches_forward():
    """Engine's first generated token == argmax of a plain forward pass."""
    from repro.models import forward

    params = init_params(jax.random.PRNGKey(0), CFG)
    eng = ServeEngine(CFG, params, n_slots=2, max_len=32)
    prompt = np.arange(1, 9, dtype=np.int32)
    req = Request(request_id="r", session_key="s", prompt=prompt,
                  max_new_tokens=1)
    eng.submit(req)
    eng.run_until_drained()
    toks = jnp.asarray(prompt)[None, :]
    pos = jnp.arange(8)[None, :]
    logits, _ = forward(params, toks, pos, CFG, mode="score")
    expected = int(jnp.argmax(logits[0, -1]))
    assert len(req.tokens) == 1
    assert int(req.tokens[0]) == expected
    assert eng.stats.host_syncs == eng.stats.ticks


def test_scheduler_fifo_pins_sessions():
    s = Scheduler(policy=DispatchPolicy.FIFO, n_replicas=4)
    reps = {s.submit(Request(request_id=f"r{i}", session_key="session-A",
                             prompt=None)) for i in range(8)}
    assert len(reps) == 1  # same session always lands on one replica
    reps_b = {s.submit(Request(request_id=f"q{i}", session_key=f"sess-{i}",
                               prompt=None)) for i in range(16)}
    assert len(reps_b) > 1  # distinct sessions spread


def test_scheduler_rr_balances():
    s = Scheduler(policy=DispatchPolicy.ROUND_ROBIN, n_replicas=3)
    counts = [0, 0, 0]
    for i in range(9):
        counts[s.submit(Request(request_id=f"r{i}", session_key="x",
                                prompt=None))] += 1
    assert counts == [3, 3, 3]


def test_admission_respects_budget():
    s = Scheduler(n_replicas=1, prefill_budget=2)
    for i in range(5):
        s.submit(Request(request_id=f"r{i}", session_key="x", prompt=None))
    first = s.admit(0, free_slots=4)
    assert len(first) == 2  # prefill budget bounds admissions per tick
    assert s.pending(0) == 3


# ----------------------------------------------------------------- sharding
def _mesh(shape=(4, 2), axes=("data", "model")):
    n = shape[0] * shape[1]
    if len(jax.devices()) < n:
        pytest.skip("needs multi-device")
    return jax.make_mesh(shape, axes)


def test_leaf_spec_dedups_mesh_axes():
    rules = {"embed": "model", "ffn": "model", "heads": "model", None: None}
    spec = leaf_spec(("embed", "ffn"), rules)
    # ffn has higher priority → gets model; embed must NOT reuse it
    assert spec == jax.sharding.PartitionSpec(None, "model")


def test_leaf_spec_priority_order():
    rules = {"expert": "data", "embed": "data", "ffn": "model", None: None}
    spec = leaf_spec(("expert", "embed", "ffn"), rules)
    assert spec == jax.sharding.PartitionSpec("data", None, "model")


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_rules_respect_divisibility(arch):
    """No rule may assign an axis that does not divide the dimension."""
    cfg = get_config(arch)

    class FakeMesh:
        axis_names = ("data", "model")
        class devices:
            shape = (16, 16)

    rules = make_rules(cfg, FakeMesh(), batch=256)
    model = 16
    if rules["heads"] == "model":
        assert cfg.n_heads % model == 0
    if rules["kv_heads"] == "model":
        assert cfg.n_kv_heads % model == 0
    if rules["vocab"] == "model":
        assert cfg.vocab_size % model == 0
    if cfg.ssm_state and rules["ssm_heads"] == "model":
        assert cfg.ssm_heads % model == 0


def test_param_axes_cover_all_archs():
    for arch in ARCH_IDS:
        cfg = get_config(arch, smoke=True)
        params = init_params(jax.random.PRNGKey(0), cfg)
        axes = param_axes(cfg)
        flat_p = jax.tree.leaves(params)
        flat_a = jax.tree.leaves(axes, is_leaf=lambda x: isinstance(x, tuple)
                                 and all(isinstance(e, (str, type(None))) for e in x))
        assert len(flat_p) == len(flat_a)
        for p, a in zip(flat_p, flat_a):
            assert p.ndim == len(a)


# ----------------------------------------------------------- mesh slices
# Multi-device tests below need >= 2 fake CPU devices: run them via
# ``make test-sharded`` (XLA_FLAGS=--xla_force_host_platform_device_count=8);
# on a plain single-device session they skip.
def test_make_host_mesh_rejects_non_divisible():
    from repro.launch.mesh import make_host_mesh

    with pytest.raises(ValueError,
                       match=r"model=4 does not divide n_devices=6"):
        make_host_mesh(6, model=4)
    with pytest.raises(ValueError, match=r"strand 2"):
        make_host_mesh(6, model=4)


def test_mesh_slices_are_disjoint_and_bounded():
    from repro.launch.mesh import mesh_slices

    n = len(jax.devices())
    if n < 2:
        pytest.skip("needs multi-device (make test-sharded)")
    slices = mesh_slices(2, 1)
    sets = [set(m.devices.flat) for m in slices]
    assert sets[0].isdisjoint(sets[1])
    with pytest.raises(ValueError, match="available"):
        mesh_slices(n + 1, 1)


def _slice_meshes(n_slices, devices_per_slice):
    from repro.launch.mesh import mesh_slices

    need = n_slices * devices_per_slice
    if len(jax.devices()) < need:
        pytest.skip(f"needs {need} devices (make test-sharded)")
    return mesh_slices(n_slices, devices_per_slice)


def _greedy_stream(eng, prompt, rid, max_new_tokens=8):
    req = Request(request_id=rid, session_key=f"s-{rid}", prompt=prompt,
                  max_new_tokens=max_new_tokens)
    eng.submit(req)
    eng.run_until_drained()
    return req, [int(t) for t in req.tokens]


def test_sharded_engine_greedy_bit_identical():
    """A model=2 sharded replica emits the bit-identical fp32 greedy stream
    of a single-device engine, keeps host_syncs == ticks on its slice, and
    its pool publishes stay zero-copy (donate_misses == 0)."""
    [mesh] = _slice_meshes(1, 2)
    params = init_params(jax.random.PRNGKey(0), CFG)
    prompt = np.arange(1, 9, dtype=np.int32)

    base = ServeEngine(CFG, params, n_slots=2, max_len=32)
    _, expected = _greedy_stream(base, prompt, rid="base")

    eng = ServeEngine(CFG, params, n_slots=2, max_len=32, mesh=mesh)
    _, got = _greedy_stream(eng, prompt, rid="sharded")

    assert got == expected and len(got) == 8
    assert eng.stats.host_syncs == eng.stats.ticks
    assert eng.cm.devstore.donate_misses == 0
    assert eng.cm.devstore.donate_hits >= eng.stats.ticks
    # the pool really is sharded over the slice: kv_heads dim on 'model'
    for leaf, sh in zip(jax.tree.leaves(eng.cm.pools),
                        jax.tree.leaves(eng.cm.pool_shardings)):
        assert len(leaf.sharding.device_set) == 2
        assert leaf.sharding == sh
        assert "model" in tuple(sh.spec)
    # params shard too (at least one leaf split over the slice)
    assert any(len(p.sharding.device_set) == 2 and
               any(ax is not None for ax in tuple(p.sharding.spec))
               for p in jax.tree.leaves(eng.params))


def test_sharded_spill_adopt_roundtrip_across_slices():
    """Spill a live session off a sharded replica and adopt it on a replica
    holding a DIFFERENT slice: every pool leaf (quantized K/V and their f32
    scales) round-trips bit-exactly, and the continued greedy stream is
    bit-identical to an uninterrupted run."""
    mesh_a, mesh_b = _slice_meshes(2, 2)
    params = init_params(jax.random.PRNGKey(0), CFG)
    prompt = np.arange(1, 9, dtype=np.int32)

    ref = ServeEngine(CFG, params, n_slots=2, max_len=32, kv_dtype="int8")
    _, expected = _greedy_stream(ref, prompt, rid="ref")

    eng_a = ServeEngine(CFG, params, n_slots=2, max_len=32, mesh=mesh_a,
                        kv_dtype="int8")
    eng_b = ServeEngine(CFG, params, n_slots=2, max_len=32, mesh=mesh_b,
                        kv_dtype="int8")
    req = Request(request_id="mig", session_key="s-mig", prompt=prompt,
                  max_new_tokens=8)
    eng_a.submit(req)
    while len(req.tokens) < 3:
        eng_a.tick()
    slot_a = req.slot
    spilled = eng_a.spill(slot_a)
    assert spilled is not None and spilled.n_blocks > 0
    # int8 pool spills 4 leaf arrays per layer stack: k, v, k_scale, v_scale
    assert len(jax.tree.leaves(spilled.blocks)) == 4
    eng_a.live.pop(slot_a)
    eng_a.cm.release(slot_a)

    assert eng_b.adopt(req, spilled)
    # round-trip: re-spilling the adopted slot off slice B returns the
    # exact bytes that left slice A, for every leaf including the scales
    back = eng_b.spill(req.slot)
    for a, b in zip(jax.tree.leaves(spilled.blocks),
                    jax.tree.leaves(back.blocks)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    eng_b.run_until_drained()
    assert [int(t) for t in req.tokens] == expected
    assert eng_a.stats.host_syncs == eng_a.stats.ticks + eng_a.stats.spill_syncs
    assert eng_b.stats.host_syncs == eng_b.stats.ticks + eng_b.stats.spill_syncs


def test_deployment_carves_disjoint_slices():
    """devices_per_replica=2 x 2 replicas: each engine owns its own slice
    (no shared devices), serves correctly, and stop() returns the devices
    to the node's pool."""
    from repro.serving.cluster import ServeCluster

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices (make test-sharded)")
    params = init_params(jax.random.PRNGKey(0), CFG)
    with ServeCluster(CFG, params, n_replicas=2, n_slots=2, max_len=32,
                      devices_per_replica=2) as cluster:
        sets = [set(jax.tree.leaves(e.cm.pools)[0].sharding.device_set)
                for e in cluster.engines]
        assert all(len(s) == 2 for s in sets)
        assert sets[0].isdisjoint(sets[1])
        # sliced replicas cannot share one jitted program (per-slice
        # out_shardings) — each compiles its own
        assert cluster.engines[0]._mixed is not cluster.engines[1]._mixed
        rng = np.random.default_rng(0)
        for i in range(4):
            cluster.submit(f"sess-{i}", f"r{i}",
                           rng.integers(0, 128, (5,)).astype(np.int32),
                           max_new_tokens=4)
        cluster.run_until_drained()
        for i in range(4):
            out = cluster.result(f"r{i}")
            assert out is not None and len(out) == 4
        for e in cluster.engines:
            assert e.stats.host_syncs == e.stats.ticks
        assert cluster.kv_store.donate_misses == 0
        node = cluster.node
        free_before_stop = len(node._free_devices)
        cluster.dep.stop()
        assert len(node._free_devices) == free_before_stop + 4


def test_paged_backend_follows_the_platform(monkeypatch):
    """Unpinned, the paged tick runs the compiled kernel on a TPU and the
    XLA path elsewhere; a pinned backend is kept; interpret mode refuses a
    TPU."""
    from repro.models.attention import paged_attn_backend

    assert CFG.attn_backend is None
    assert paged_attn_backend(CFG) == "xla"
    assert paged_attn_backend(CFG.replace(attn_backend="pallas_interpret")) \
        == "pallas_interpret"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert paged_attn_backend(CFG) == "pallas"
    assert paged_attn_backend(CFG.replace(attn_backend="xla")) == "xla"
    with pytest.raises(ValueError, match="interpret"):
        paged_attn_backend(CFG.replace(attn_backend="pallas_interpret"))


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
@pytest.mark.parametrize("devices", [1, 2])
def test_sliced_kernel_matches_single_device(devices, kv_dtype):
    """On a mesh slice the ragged kernel runs per shard (shard_map over
    'model', each shard on its own kv heads): greedy streams and scores
    match the single-device engine running the same interpreted kernel."""
    [mesh] = _slice_meshes(1, devices)
    cfg = CFG.replace(attn_backend="pallas_interpret")
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompt = np.arange(1, 12, dtype=np.int32)

    base = ServeEngine(cfg, params, n_slots=2, max_len=32, kv_dtype=kv_dtype)
    ref, expected = _greedy_stream(base, prompt, rid="base", max_new_tokens=5)

    eng = ServeEngine(cfg, params, n_slots=2, max_len=32, mesh=mesh,
                      kv_dtype=kv_dtype)
    req, got = _greedy_stream(eng, prompt, rid="sliced", max_new_tokens=5)

    assert got == expected and len(got) == 5
    np.testing.assert_allclose(req.scores, ref.scores, atol=1e-5)
    assert eng.stats.host_syncs == eng.stats.ticks
    assert eng.cm.devstore.donate_misses == 0
    if devices > 1:     # a 1-device shard_map lowers to the plain call
        assert "sdy.manual_computation" in eng.lower_tick().as_text()
