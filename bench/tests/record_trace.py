"""Record the small chip trace the trace-reduction test reads.

    python bench/tests/record_trace.py --workload danube18b.chat --out PATH

Runs the cell's set-up and a one-second traced window, and writes the
device planes' ``XLA Ops`` lines and the host's ``bench.*`` spans, as
plain data (``harness.trace.load``'s format), to PATH.
"""
import argparse
import glob
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="danube18b.chat")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()

    from bench.harness import cell_run, spec, trace

    cell = spec.load_cell(ROOT, args.workload)
    cell_run.use_compile_cache(cell.bench_dir / ".jax_cache")
    params = cell_run.build(cell, args.seed)
    served = cell_run.serve(cell, params, args.seed, 1.0, True,
                            t_proc0=time.monotonic(),
                            compiles=cell_run.CompileCounter())
    path = glob.glob(f"{served.trace_dir}/plugins/profile/*/*.xplane.pb")[0]
    planes = trace.load(path)
    for p in planes:
        print(p["name"], [(ln["name"], len(ln["events"]))
                          for ln in p["lines"]], file=sys.stderr)
    keep = []
    for p in planes:
        lines = [{"name": ln["name"],
                  "events": [e for e in ln["events"]
                             if e[0].startswith("bench.")]}
                 if not p["name"].startswith("/device:") else ln
                 for ln in p["lines"]
                 if not p["name"].startswith("/device:")
                 or ln["name"] == trace.OPS_LINE]
        lines = [ln for ln in lines if ln["events"]]
        if lines:
            keep.append({"name": p["name"], "lines": lines})
    Path(args.out).write_text(json.dumps({"planes": keep}))
    r = trace.reduce(keep)
    print(json.dumps({"busy_s": r.busy_s, "window_s": r.window_s,
                      "top_ops": r.top_ops, "idle_gaps": r.idle_gaps}))


if __name__ == "__main__":
    main()
