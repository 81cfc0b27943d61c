"""Run one cell once, traced, and print where its time goes by the
program's own phases.

    python bench/phases.py --workload <cell> --seed <n> --seconds <s>
                           [--record PATH]

The run is ``bench/run.py --trace 1``'s set-up and window, with no check
of ``correct``.  After the window the deployment is made again, only to
read the compiled tick's HLO text (a load from the compile cache), which
names each device op's program scope (``harness.phases``).  The last line
of standard output is a JSON object: the cell's per-layer metrics, device
self time by program scope, the program's host spans (``cascade.*``) in
the window, the tick's attention-grid counts, idle time by the span that
held it and the longest idle gaps, the upcall delivery and the top ops with
their scopes and result shapes.  ``--record`` also writes the trace as
plain data, gzipped (``phases.record``), for a test to read.

Exits non-zero when JAX finds no accelerator.
"""
import time

T_PROC0 = time.monotonic()

import argparse  # noqa: E402
import glob  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def tick_hlo(cell, params) -> str:
    """The compiled tick's HLO text, from a deployment made as the run's
    was (``cell_run.serve``)."""
    from repro.serving.cluster import ServeNode

    dep_cfg = cell.config["deployment"]
    kw = {k: dep_cfg[k] for k in ("num_blocks", "kv_dtype",
                                  "devices_per_replica")
          if dep_cfg.get(k) is not None}
    node = ServeNode(n_workers=1)
    try:
        dep = node.deploy(cell.name,
                          cell.model.program_config(cell.config_name,
                                                    cell.config),
                          params, n_replicas=1, n_slots=dep_cfg["n_slots"],
                          max_len=dep_cfg["max_len"], **kw)
        text = dep.engines[0].lower_tick().compile().as_text()
        node.undeploy(cell.name)
    finally:
        node.close()
    return text


def summary(cell, served, ph, ctx, ops: dict) -> dict:
    """The numbers this tool prints, from one traced run; ``ops`` maps an
    instruction of the tick to its scope and result shape."""
    from bench.harness import spec
    from bench.harness.drive import pct

    base = ph.base
    metrics = {}
    for m in cell.per_layer:
        v = spec.metric_reader(cell, m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = v
    ticks = ph.ticks()
    c = ph.counts
    grid, live = c.get("attn_grid_blocks", 0), c.get("attn_live_blocks", 0)
    # requests finished in the window: those in flight have no Request yet
    upcall = [r.req.enqueued_s - r.req.arrived_s
              for r in served.drive.finished_in_window()
              if getattr(r.req, "enqueued_s", None) is not None]
    return {
        "metrics": metrics,
        "busy_s": base.busy_s, "window_s": base.window_s,
        "scope_share": {k: 100.0 * v / base.busy_s
                        for k, v in sorted(ph.scope_s.items(),
                                           key=lambda kv: -kv[1])},
        "scope_named_share": 100.0 * ph.mapped,
        "kv_pool_share": 100.0 * ph.share("kv_pool"),
        "unscoped_share": 100.0 * ph.scope_s.get("", 0.0) / base.busy_s,
        "spans_s": ph.span_s, "spans_n": ph.span_n,
        "ticks": ticks,
        "tick_host_ms": (1e3 * (ph.span_s.get("cascade.tick.pack", 0.0)
                                + ph.span_s.get("cascade.tick.emit", 0.0))
                         / ticks if ticks else None),
        "attn_counts": c,
        "attn_live_share": 100.0 * live / grid if grid else None,
        "attn_pad_share_of_live": (100.0 * c.get("attn_pad_blocks", 0) / live
                                   if live else None),
        "idle_s": dict(sorted(ph.idle_s.items(), key=lambda kv: -kv[1])),
        "idle_gaps": ph.idle_gaps,
        "upcall_delivery_p95_s": pct(upcall, 95) if upcall else None,
        "top_ops": [[n, s, *ops.get(n, (None, None))]
                    for n, s in base.top_ops],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--record", default=None)
    args = ap.parse_args(argv)

    import jax

    from bench.harness import cell_run, phases, spec

    cell = spec.load_cell(ROOT, args.workload)
    devs = jax.devices()
    if devs[0].platform == "cpu" or len(devs) < cell.chips:
        print(f"bench: cell {cell.name} needs {cell.chips} accelerator "
              f"chip(s)", file=sys.stderr)
        return 2
    cell_run.use_compile_cache(cell.bench_dir / ".jax_cache")
    compiles = cell_run.CompileCounter()
    params = cell_run.build(cell, args.seed)
    served = cell_run.serve(cell, params, args.seed, args.seconds, True,
                            t_proc0=T_PROC0, compiles=compiles)
    cell_run.report(served)
    hlo = tick_hlo(cell, params)
    path = glob.glob(f"{served.trace_dir}/plugins/profile/*/*.xplane.pb")[0]
    rec = phases.load(path, hlo)
    shutil.rmtree(served.trace_dir, ignore_errors=True)
    if args.record:
        small = dict(phases.record(rec), source=(
            f"bench/phases.py --workload {cell.name} --seed {args.seed} "
            f"--seconds {args.seconds}, {devs[0].device_kind}"))
        Path(args.record).write_bytes(gzip.compress(
            json.dumps(small).encode()))
    ph = phases.reduce(rec)
    d = served.drive
    ctx = cell_run.Context(
        cell=cell, arch=cell.config, drive=d, setup_s=served.setup_s,
        window_s=d.close_s - d.open_s, token_budget=served.token_budget,
        chips=len(served.devices), device_kind=devs[0].device_kind,
        trace=ph.base)
    shapes = dict(re.findall(r"^\s*(?:ROOT )?%(\S+) = (\S+)", hlo, re.M))
    out = summary(cell, served, ph, ctx, {
        n: (phases.scope_of(o), shapes.get(n))
        for n, o in rec["op_names"].items()})
    out["setup_s"] = served.setup_s
    out["device"] = cell_run.device_info()
    for k in ("idle_s", "idle_gaps", "spans_s", "attn_counts"):
        print(f"{k}: {json.dumps(out[k])}", file=sys.stderr)
    print(json.dumps(out, allow_nan=False), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
