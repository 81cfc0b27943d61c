"""Compile-only checks for a TPU v5e that is described, not attached.

The TPU compiler compiles for a described topology from a CPU-only host, so
the main path's kernels and step are compiled here at h2o-danube widths —
refusals Mosaic makes (tiling, VMEM, partitioning) fail here at no chip
time.  Nothing runs: these say nothing about results or speed.

The topology is described inside a module-scoped fixture, never at import,
so every test worker collects the same tests and only the worker given this
file loads the TPU library.  The persistent compilation cache is off around
these compiles: an entry written for a described chip cannot be read back
without one.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config
from repro.kernels.decode_attention import ops as da_ops
from repro.launch.sharding import kv_pool_shardings, param_shardings
from repro.models import (init_paged_pools, init_params, paged_mixed_step,
                          param_axes)
from repro.models.attention import _ragged_kernel_attention

DANUBE = get_config("h2o-danube-1.8b")          # T=64, H=32, K=8, D=80
DANUBE3 = get_config("h2o-danube-3-4b")         # H=32, K=8, D=120


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh(topo):
    return Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"))


def _on(sharding, tree):
    """Shapes of ``tree`` (arrays or ShapeDtypeStructs) placed on
    ``sharding`` — one sharding, or a congruent tree of them."""
    shardings = (sharding if not isinstance(sharding, jax.sharding.Sharding)
                 else jax.tree.map(lambda _: sharding, tree))
    return jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        tree, shardings)


def _kernel_operands(cfg, *, T, num_blocks, block_size, kv_dtype):
    H, K, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    pool = jax.eval_shape(lambda: init_paged_pools(
        cfg, num_blocks, block_size, kv_dtype=kv_dtype))[0][0]
    pool = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
                        pool)                            # one layer's share
    nb = cfg.max_target_length // block_size
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    return (jax.ShapeDtypeStruct((T, H, D), jnp.bfloat16), pool,
            i32(8, nb), i32(T), i32(T))


@pytest.mark.parametrize("kv_dtype,block_size", [
    ("bfloat16", 16), ("int8", 16), ("float32", 16), ("bfloat16", 128)])
def test_ragged_kernel_compiles_at_danube_widths(one_chip, kv_dtype,
                                                 block_size):
    """head_dim 80 is no multiple of 128, and the int8 pool brings (bs, 1)
    scale tiles: both are shapes Mosaic may refuse."""
    q, pool, bt, rows, pos = _on(one_chip, _kernel_operands(
        DANUBE, T=64, num_blocks=2561, block_size=block_size,
        kv_dtype=kv_dtype))

    def attend(q, pool, bt, rows, pos):
        return da_ops.ragged_paged_attention(
            q, pool["k"], pool["v"], bt, rows, pos,
            k_scale=pool.get("k_scale"), v_scale=pool.get("v_scale"),
            window=DANUBE.window)

    hlo = jax.jit(attend).lower(q, pool, bt, rows, pos).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_full_width_tick_compiles_for_one_chip(one_chip):
    """The whole unified tick of h2o-danube-1.8b (24 layers, bf16, the
    default 2,561-block pool of 8 slots x 4096 tokens, budget 256) fits one
    v5e's 16 GB with one kernel call in it."""
    cfg = DANUBE.replace(attn_backend="pallas")
    params = _on(one_chip, jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)))
    pools = _on(one_chip, jax.eval_shape(
        lambda: init_paged_pools(cfg, 2561, 16)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    T, R = 256, 8

    def tick(p, pools, bt, toks, pos, rows, sample_idx):
        return paged_mixed_step(p, pools, bt, toks, pos, rows, sample_idx,
                                cfg)

    compiled = jax.jit(tick, donate_argnums=(1,)).lower(
        params, pools, i32(R, 4096 // 16), i32(T), i32(T), i32(T),
        i32(R)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
def test_sharded_kernel_compiles_on_2x2_mesh(mesh, kv_dtype):
    """Under a mesh the kernel runs per shard through shard_map; without it
    Mosaic refuses to partition the call."""
    q, pool, bt, rows, pos = _kernel_operands(
        DANUBE3, T=256, num_blocks=385, block_size=16, kv_dtype=kv_dtype)
    heads = NamedSharding(mesh, P(None, "model", None))
    kv = {"k": NamedSharding(mesh, P(None, None, "model", None)),
          "v": NamedSharding(mesh, P(None, None, "model", None)),
          "k_scale": NamedSharding(mesh, P(None, None, "model")),
          "v_scale": NamedSharding(mesh, P(None, None, "model"))}
    rep = NamedSharding(mesh, P())
    q = _on(heads, q)
    pool = {n: _on(kv[n], leaf) for n, leaf in pool.items()}
    bt, rows, pos = _on(rep, (bt, rows, pos))

    def attend(q, pool, bt, rows, pos):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return _ragged_kernel_attention(
                q, pool, bt, rows, pos, window=DANUBE3.window, cap=None,
                scale=DANUBE3.head_dim ** -0.5, interpret=False)

    compiled = jax.jit(attend).lower(q, pool, bt, rows, pos).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    assert "all-gather" not in hlo


def test_query_heads_line_up_with_their_kv_heads_per_shard(mesh):
    """On every device of a 4-way slice, the query heads ``wq`` holds are
    exactly the G-groups of the kv heads the pool shard holds, so the
    per-shard kernel's (K/m, G) view is the global one restricted."""
    cfg = DANUBE3
    G = cfg.n_heads // cfg.n_kv_heads
    wq_sharding = param_shardings(cfg, mesh)["segments"][0][0]["attn"]["wq"]
    pool_sharding = kv_pool_shardings(cfg, mesh)[0][0]["k"]
    assert param_axes(cfg)["segments"][0][0]["attn"]["wq"][2] == "heads"
    wq_shape = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.head_dim)
    pool_shape = (cfg.n_layers, 385, 16, cfg.n_kv_heads, cfg.head_dim)
    wq_idx = wq_sharding.devices_indices_map(wq_shape)
    pool_idx = pool_sharding.devices_indices_map(pool_shape)
    for dev in mesh.devices.flat:
        heads = range(cfg.n_heads)[wq_idx[dev][2]]
        kv_heads = range(cfg.n_kv_heads)[pool_idx[dev][3]]
        assert len(kv_heads) == cfg.n_kv_heads // 4
        assert sorted({h // G for h in heads}) == list(kv_heads)
        assert len(heads) == G * len(kv_heads)
