"""Multi-pod dry-run: lower + compile every (arch × shape) cell on the
production mesh, prove memory fit, and extract roofline terms.

MUST set the placeholder device count before ANY jax import (jax locks the
device count on first init) — hence the first lines.  The platform is pinned
to the CPU in the same place, so this lowering tool (and every per-cell child
it starts, which inherits the environment) never claims an accelerator.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

import argparse
import json
import re
import subprocess
import sys
import time
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.registry import ARCH_IDS, SHAPES, Cell, all_cells, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch.sharding import (batch_shardings, make_rules, opt_state_axes,
                                   tree_shardings)
from repro.models import (cache_axes, decode_step, forward, init_decode_caches,
                          init_params, param_axes, prefill)
from repro.models.config import ModelConfig
from repro.training.optimizer import get_optimizer
from repro.training.train import TrainState, make_train_step

# ----------------------------------------------------------- hardware model
PEAK_FLOPS = 197e12        # bf16 FLOP/s per chip (TPU v5e class)
HBM_BW = 819e9             # B/s per chip
ICI_BW = 50e9              # B/s per chip (per-link figure per assignment)

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_SHAPE_RE = re.compile(r"\b([a-z]+\d+(?:_\d+)?)\[([\d,]*)\]")
_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8_e4m3": 1, "f8_e5m2": 1, "s4": 1, "u4": 1}


def _nbytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


_GROUP_RE = re.compile(r"replica_groups=\[\d+,(\d+)\]")


def parse_collective_bytes(hlo_text: str) -> dict[str, int]:
    """Per-device bytes moved by every collective in compiled HLO text.

    Post-optimization HLO annotates only RESULT types, so we parse those and
    apply a per-op ring-transfer model (g = replica group size):
      all-reduce        ≈ 2·result·(g-1)/g   (reduce-scatter + all-gather ring)
      all-gather        ≈ result·(g-1)/g     (result is the gathered size)
      reduce-scatter    ≈ result·(g-1)      (operand = result·g, ring (g-1)/g)
      all-to-all        ≈ result·(g-1)/g
      collective-permute≈ result             (point-to-point)
    """
    out: dict[str, int] = {c: 0 for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        m = re.match(r"%?[\w.\-]+\s*=\s*", s)
        if m is None:
            continue
        rest = s[m.end():]
        opm = re.search(r"\b(" + "|".join(_COLLECTIVES) + r")\(", rest)
        if opm is None:
            continue
        op = opm.group(1)
        result_part = rest[:opm.start()]
        rbytes = sum(_nbytes(dt, dims)
                     for dt, dims in _SHAPE_RE.findall(result_part))
        gm = _GROUP_RE.search(s)
        g = int(gm.group(1)) if gm else 2
        ring = (g - 1) / g
        if op == "all-reduce":
            moved = 2 * rbytes * ring
        elif op == "all-gather":
            moved = rbytes * ring
        elif op == "reduce-scatter":
            moved = rbytes * (g - 1)
        elif op == "all-to-all":
            moved = rbytes * ring
        else:  # collective-permute
            moved = rbytes
        out[op] += int(moved)
    return out


# ----------------------------------------------------------- input specs
def input_specs(arch_id: str, shape_name: str, *, multi_pod: bool = False,
                cfg: ModelConfig | None = None) -> dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input of a cell (weak-type
    correct, shardable, no allocation)."""
    cfg = cfg or get_config(arch_id)
    shape = SHAPES[shape_name]
    B, S = shape.global_batch, shape.seq_len
    tok_dt = jnp.int32
    emb_dt = jnp.dtype(cfg.dtype)

    if shape.kind == "train":
        inputs = (jax.ShapeDtypeStruct((B, S, cfg.d_model), emb_dt)
                  if cfg.input_mode == "embeds"
                  else jax.ShapeDtypeStruct((B, S), tok_dt))
        return {
            "inputs": inputs,
            "targets": jax.ShapeDtypeStruct((B, S), tok_dt),
            "positions": jax.ShapeDtypeStruct((B, S), tok_dt),
            "mask": jax.ShapeDtypeStruct((B, S), jnp.float32),
        }
    if shape.kind == "prefill":
        inputs = (jax.ShapeDtypeStruct((B, S, cfg.d_model), emb_dt)
                  if cfg.input_mode == "embeds"
                  else jax.ShapeDtypeStruct((B, S), tok_dt))
        return {"inputs": inputs,
                "positions": jax.ShapeDtypeStruct((B, S), tok_dt)}
    # decode: one new token against a seq_len cache
    inputs = (jax.ShapeDtypeStruct((B, 1, cfg.d_model), emb_dt)
              if cfg.input_mode == "embeds"
              else jax.ShapeDtypeStruct((B,), tok_dt))
    return {"inputs": inputs,
            "positions": jax.ShapeDtypeStruct((B, 1), tok_dt)}


# ----------------------------------------------------------- cell lowering
def lower_cell(arch_id: str, shape_name: str, *, multi_pod: bool,
               overrides: dict | None = None):
    overrides = dict(overrides or {})
    rule_overrides = overrides.pop("_rules", None)   # sharding-rule overrides
    grad_accum = int(overrides.pop("_grad_accum", 1))
    cfg = get_config(arch_id)
    if overrides:
        cfg = cfg.replace(**overrides)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = make_rules(cfg, mesh, batch=shape.global_batch)
    if rule_overrides:
        for k, v in rule_overrides.items():
            rules[k] = tuple(v) if isinstance(v, list) else v

    p_axes = param_axes(cfg)
    p_shard = tree_shardings(p_axes, mesh, rules)
    params_shapes = jax.eval_shape(lambda k: init_params(k, cfg),
                                   jax.ShapeDtypeStruct((2,), jnp.uint32))
    specs = input_specs(arch_id, shape_name, cfg=cfg)
    b_shard = batch_shardings(specs, mesh, rules)

    if shape.kind == "train":
        from repro.launch.sharding import _is_axes_leaf, leaf_spec
        opt = get_optimizer(cfg.optimizer)
        opt_shapes = jax.eval_shape(opt.init, params_shapes)
        o_axes = opt_state_axes(opt.name, p_axes)
        o_shard = jax.tree.map(
            lambda a: NamedSharding(mesh, leaf_spec(a, rules)), o_axes,
            is_leaf=_is_axes_leaf)
        state_shapes = TrainState(params=params_shapes, opt_state=opt_shapes)
        state_shard = TrainState(params=p_shard, opt_state=o_shard)
        step_fn = make_train_step(cfg, opt, grad_accum=grad_accum)
        jitted = jax.jit(step_fn, in_shardings=(state_shard, b_shard),
                         out_shardings=(state_shard, None),
                         donate_argnums=(0,))
        with mesh:
            lowered = jitted.lower(state_shapes, specs)
        return lowered, cfg, mesh

    if shape.kind == "prefill":
        def prefill_fn(params, inputs, positions):
            return prefill(params, inputs, positions, cfg, max_len=shape.seq_len)

        jitted = jax.jit(prefill_fn,
                         in_shardings=(p_shard, b_shard["inputs"],
                                       b_shard["positions"]))
        with mesh:
            lowered = jitted.lower(params_shapes, specs["inputs"],
                                   specs["positions"])
        return lowered, cfg, mesh

    # decode
    cache_shapes = jax.eval_shape(
        lambda: init_decode_caches(cfg, shape.global_batch, shape.seq_len))
    c_shard = tree_shardings(cache_axes(cfg), mesh, rules)

    def serve_step(params, caches, inputs, positions):
        return decode_step(params, caches, inputs, positions, cfg)

    jitted = jax.jit(serve_step,
                     in_shardings=(p_shard, c_shard, b_shard["inputs"],
                                   b_shard["positions"]),
                     out_shardings=(None, c_shard),
                     donate_argnums=(1,))
    with mesh:
        lowered = jitted.lower(params_shapes, cache_shapes, specs["inputs"],
                               specs["positions"])
    return lowered, cfg, mesh


def model_flops(cfg: ModelConfig, shape) -> float:
    """6·N_active·D reference FLOPs for the cell (decode: D = batch tokens)."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n * shape.global_batch * shape.seq_len
    return 2.0 * n * shape.global_batch      # one token per sequence


def _measure(arch_id: str, shape_name: str, *, multi_pod: bool,
             overrides: dict | None) -> dict:
    """Lower+compile one variant; return raw per-device costs."""
    t0 = time.time()
    lowered, cfg, mesh = lower_cell(arch_id, shape_name, multi_pod=multi_pod,
                                    overrides=overrides)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    coll = parse_collective_bytes(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll": coll,
        "mem": mem,
        "chips": int(mesh.devices.size),
        "t_lower": t_lower,
        "t_compile": t_compile,
        "cfg": cfg,
    }


def _attn_chunk_topup(cfg: ModelConfig, shape, mesh) -> float:
    """Analytic per-chip attention FLOPs hidden by the q-chunk inner scan.

    The chunked-attention scan body (one q-chunk vs full K) is counted once
    by cost_analysis, i.e. 1/n_chunks of the attention einsum FLOPs; this
    returns the missing (n_chunks-1)/n_chunks share.  Train steps pay the
    attention ~4× (fwd + remat recompute + bwd dq/dk·dv), prefill 1×.
    """
    S = shape.seq_len
    nc = -(-S // cfg.q_chunk)
    if shape.kind == "decode" or nc <= 1:
        return 0.0
    n_attn = sum(sum(1 for s in seg.pattern if s.kind != "mamba") * seg.repeat
                 for seg in cfg.layout())
    if n_attn == 0:
        return 0.0
    # QKᵀ + PV einsums, unmasked (the impl masks but computes full blocks)
    per_layer = 4.0 * shape.global_batch * S * S * cfg.n_heads * cfg.head_dim
    mult = 4.0 if (shape.kind == "train" and cfg.remat) else \
        (3.0 if shape.kind == "train" else 1.0)
    total = per_layer * n_attn * mult * (nc - 1) / nc
    # per-chip divisor: batch over data(+pod); heads over model when sharded
    axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    div = 1
    if shape.global_batch % max(1, axes.get("data", 1)) == 0:
        div *= axes.get("data", 1)
        if "pod" in axes and shape.global_batch % (axes["pod"] * axes["data"]) == 0:
            div *= axes["pod"]
    if cfg.n_heads % max(1, axes.get("model", 1)) == 0:
        div *= axes.get("model", 1)
    return total / div


def corrected_costs(arch_id: str, shape_name: str, *, multi_pod: bool,
                    overrides: dict | None) -> dict:
    """Scan-aware costs: XLA cost_analysis counts while-loop bodies ONCE, so
    we lower repeat=1 and repeat=2 UNROLLED ladder variants per segment and
    scale the per-body diff by the true trip count.  The inner q-chunk
    attention scan is topped up analytically (_attn_chunk_topup)."""
    cfg_overrides = {k: v for k, v in (overrides or {}).items()
                     if not k.startswith("_")}
    base_cfg = get_config(arch_id)
    if cfg_overrides:
        base_cfg = base_cfg.replace(**cfg_overrides)
    segs = base_cfg.layout()
    ones = tuple(1 for _ in segs)
    shape = SHAPES[shape_name]

    ov = dict(overrides or {})
    ov.pop("_grad_accum", None)   # roofline terms measured at accum=1
    ov["layout_repeats"] = ones
    ov["scan_unroll"] = True       # unrolled bodies are visible to cost_analysis
    base = _measure(arch_id, shape_name, multi_pod=multi_pod, overrides=ov)
    flops = base["flops"]
    nbytes = base["bytes"]
    coll = dict(base["coll"])
    for i, seg in enumerate(segs):
        if seg.repeat <= 1:
            continue
        reps = list(ones)
        reps[i] = 2
        ov2 = dict(ov, layout_repeats=tuple(reps))
        two = _measure(arch_id, shape_name, multi_pod=multi_pod, overrides=ov2)
        mult = seg.repeat - 1
        flops += mult * (two["flops"] - base["flops"])
        nbytes += mult * (two["bytes"] - base["bytes"])
        for k in coll:
            coll[k] += mult * (two["coll"][k] - base["coll"][k])
    mesh = make_production_mesh(multi_pod=multi_pod)
    flops += _attn_chunk_topup(base_cfg, shape, mesh)
    return {"flops": flops, "bytes": nbytes,
            "coll": {k: max(0.0, v) for k, v in coll.items()}}


def run_cell(arch_id: str, shape_name: str, *, multi_pod: bool,
             out_dir: str, overrides: dict | None = None,
             tag: str = "", calibrate: bool = True) -> dict:
    full = _measure(arch_id, shape_name, multi_pod=multi_pod,
                    overrides=overrides)
    cfg, mem, n_chips = full["cfg"], full["mem"], full["chips"]
    shape = SHAPES[shape_name]
    mf = model_flops(cfg, shape)

    if calibrate and not multi_pod:
        corr = corrected_costs(arch_id, shape_name, multi_pod=multi_pod,
                               overrides=overrides)
        flops, bytes_accessed = corr["flops"], corr["bytes"]
        coll = corr["coll"]
    else:
        flops, bytes_accessed, coll = full["flops"], full["bytes"], full["coll"]
    coll_bytes = float(sum(coll.values()))

    result = {
        "arch": arch_id, "shape": shape_name, "multi_pod": multi_pod,
        "tag": tag, "chips": n_chips, "calibrated": calibrate and not multi_pod,
        "seconds": {"lower": round(full["t_lower"], 1),
                    "compile": round(full["t_compile"], 1)},
        "memory_analysis": {
            "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            "code_bytes": int(getattr(mem, "generated_code_size_in_bytes", 0)),
        },
        "cost_analysis": {"flops": flops, "bytes_accessed": bytes_accessed,
                          "raw_flops_uncorrected": full["flops"]},
        "collective_bytes": coll,
        "roofline": {
            # cost_analysis is per-device post-SPMD; terms are per-chip step time
            "compute_s": flops / PEAK_FLOPS,
            "memory_s": bytes_accessed / HBM_BW,
            "collective_s": coll_bytes / ICI_BW,
            "model_flops_global": mf,
            "useful_flops_ratio": (mf / n_chips) / flops if flops else 0.0,
        },
    }
    dom = max(("compute_s", "memory_s", "collective_s"),
              key=lambda k: result["roofline"][k])
    result["roofline"]["dominant"] = dom
    os.makedirs(out_dir, exist_ok=True)
    suffix = ("_" + tag if tag else "") + ("_multipod" if multi_pod else "")
    fname = f"{arch_id}__{shape_name}{suffix}.json".replace("/", "_")
    with open(os.path.join(out_dir, fname), "w") as f:
        json.dump(result, f, indent=2)
    return result


# ----------------------------------------------------------------- CLI
def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true", help="run every cell in subprocesses")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--tag", default="")
    ap.add_argument("--override", action="append", default=[],
                    help="cfg field=value overrides (e.g. moe_impl=scatter)")
    args = ap.parse_args()

    if args.all:
        failures = []
        jobs = []
        size_rank = {a: get_config(a).param_count() for a in ARCH_IDS}
        kind_rank = {"decode": 0, "prefill": 1, "train": 2}
        for cell in all_cells():
            if cell.skipped:
                print(f"SKIP {cell.name}: {cell.skip_reason}", flush=True)
                continue
            meshes = [False, True] if args.both_meshes else [args.multi_pod]
            for mp in meshes:
                jobs.append((cell, mp))
        # cheapest first: decode < prefill < train, small models first,
        # single-pod (with calibration) before multi-pod
        jobs.sort(key=lambda j: (kind_rank[j[0].shape.kind],
                                 size_rank[j[0].arch_id], j[1]))
        for cell, mp in jobs:
            suffix = ("_" + args.tag if args.tag else "") + ("_multipod" if mp else "")
            fname = f"{cell.arch_id}__{cell.shape.name}{suffix}.json"
            if os.path.exists(os.path.join(args.out, fname)):
                print(f"HAVE {cell.name} multi_pod={mp}", flush=True)
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", cell.arch_id, "--shape", cell.shape.name,
                   "--out", args.out]
            if mp:
                cmd.append("--multi-pod")
            for ov in args.override:
                cmd += ["--override", ov]
            if args.tag:
                cmd += ["--tag", args.tag]
            print(f"RUN  {cell.name} multi_pod={mp}", flush=True)
            t0 = time.time()
            r = subprocess.run(cmd, capture_output=True, text=True)
            if r.returncode != 0:
                failures.append((cell.name, mp, r.stderr[-2000:]))
                print(f"FAIL {cell.name}: {r.stderr[-500:]}", flush=True)
            else:
                last = r.stdout.strip().splitlines()[-1] if r.stdout.strip() else "ok"
                print(f"  [{time.time()-t0:5.0f}s] {last}", flush=True)
        if failures:
            print(f"\n{len(failures)} FAILURES")
            for name, mp, err in failures:
                print(f"--- {name} mp={mp}\n{err}\n")
            sys.exit(1)
        print("\nALL CELLS PASS")
        return

    overrides = {}
    for ov in args.override:
        k, v = ov.split("=", 1)
        try:
            v = json.loads(v)
        except json.JSONDecodeError:
            pass
        overrides[k] = v
    res = run_cell(args.arch, args.shape, multi_pod=args.multi_pod,
                   out_dir=args.out, overrides=overrides or None, tag=args.tag)
    r = res["roofline"]
    print(f"{args.arch}@{args.shape} mp={args.multi_pod} "
          f"compute={r['compute_s']:.4f}s memory={r['memory_s']:.4f}s "
          f"collective={r['collective_s']:.4f}s dominant={r['dominant']} "
          f"useful={r['useful_flops_ratio']:.2f} "
          f"temp={res['memory_analysis']['temp_bytes']/2**30:.2f}GiB")


if __name__ == "__main__":
    main()
