"""Compile each cell's tick at its real size for a described TPU v5e.

    JAX_PLATFORMS=cpu python bench/rehearse.py [cell ...]
    JAX_PLATFORMS=cpu python bench/rehearse.py --slice4 <cell> <n_slots>

No chip is needed and nothing runs: the TPU compiler compiles the unified
tick (the program's ``paged_mixed_step`` and sampler, with the compiled
ragged kernel) for one described chip from shapes alone; ``--slice4``
compiles a cell's configuration as one replica on a described 2x2 slice
(``devices_per_replica=4``: weights and pool sharded over the slice's
'model' axis, the kernel per shard) with ``n_slots`` slots.  It prints
each program's ``memory_analysis``: the weights and pool it takes as
arguments and the temporaries it needs, against the chip's HBM.  A compile that passes is not
a run on the chip.
"""
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def rehearse(cell, topo, n_slots=None, slice4=False) -> dict:
    import contextlib

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                              SingleDeviceSharding)

    from bench.harness.model import make_weights
    from repro.launch.sharding import kv_pool_shardings, param_shardings
    from repro.models import init_paged_pools, paged_mixed_step
    from repro.models.sampling import speculative_verify

    arch, dep = cell.config, cell.config["deployment"]
    cfg = cell.model.program_config(cell.config_name, arch).replace(
        attn_backend="pallas")
    n, max_len, bs = n_slots or dep["n_slots"], dep["max_len"], 16
    nb = -(-max_len // bs)
    num_blocks = dep["num_blocks"] or 1 + (n + 2) * nb     # engine default
    T = max(32, 2 * n)                                     # engine default
    params = jax.eval_shape(
        lambda: make_weights(cell.model.weight_shapes(arch), 0))
    pools = jax.eval_shape(lambda: init_paged_pools(
        cfg, num_blocks, bs, kv_dtype=dep["kv_dtype"]))
    if slice4:
        mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"))
        rep = NamedSharding(mesh, PartitionSpec())
        p_sh = param_shardings(cfg, mesh)
        kv_sh = kv_pool_shardings(cfg, mesh, kv_dtype=dep["kv_dtype"])
        scope = jax.sharding.use_abstract_mesh(mesh.abstract_mesh)
    else:
        rep = SingleDeviceSharding(topo.devices[0])
        p_sh = jax.tree.map(lambda _: rep, params)
        kv_sh = jax.tree.map(lambda _: rep, pools)
        scope = contextlib.nullcontext()

    def on(tree, shardings):
        return jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=s), tree, shardings)

    params, pools = on(params, p_sh), on(pools, kv_sh)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=rep)

    def tick(p, pools, bt, toks, pos, rows, sample_idx, draft_toks,
             draft_len, seed):
        with scope:
            logits, pools = paged_mixed_step(p, pools, bt, toks, pos, rows,
                                             sample_idx, cfg)
        tok, n_acc, score = speculative_verify(logits, draft_toks, draft_len,
                                               seed, 0.0)
        return tok, n_acc, score, pools

    compiled = jax.jit(tick, donate_argnums=(1,),
                       out_shardings=(rep, rep, rep, kv_sh)).lower(
        params, pools, i32(n, nb), i32(T), i32(T), i32(T), i32(n, 1),
        i32(n, 0), i32(n), i32()).compile()
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    return {"cell": cell.name, "slots": n, "devices": 4 if slice4 else 1,
            "num_blocks": num_blocks, "token_budget": T,
            "kernel": "tpu_custom_call" in hlo,
            "all_reduces": hlo.count(" all-reduce("),
            "all_gathers": hlo.count(" all-gather("),
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes}


def main() -> None:
    import json

    import jax
    from jax.experimental import topologies

    from bench.harness import spec

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    if sys.argv[1:2] == ["--slice4"]:
        cell = spec.load_cell(ROOT, sys.argv[2])
        print(json.dumps(rehearse(cell, topo, int(sys.argv[3]), True)))
        return
    names = sys.argv[1:] or [w["name"] for w in json.loads(
        (ROOT / "BENCHMARK.json").read_text())["workloads"]]
    for name in names:
        cell = spec.load_cell(ROOT, name)
        if cell.chips != 1:
            print(json.dumps({"cell": name, "skipped": "four-chip cells "
                              "compile under their slice's mesh"}))
            continue
        print(json.dumps(rehearse(cell, topo)), flush=True)


if __name__ == "__main__":
    main()
