"""The readings ``correct``'s limit is set from: the program's and the
control's, on the same served requests, over several seeds in one process.

    python bench/control.py --workload <cell> --seeds 11 12 13 --seconds 51

For each seed: the cell's weights, its deployment, its warm phase and a
window of ``--seconds`` at the cell's own load, exactly as a run makes them;
then, on the sample a run compares, the program's reading (the widest gap of
a served token below the reference's best logit) and the control's (the
reference computed with every matrix product's operands rounded to fp8,
the step below the bf16 the configurations state: the widest gap of the
token it puts first).  One JSON line per seed.  The benchmark's own runs do
not run this.
"""
import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()

    import jax

    from bench.harness import cell_run, correct, spec

    cell = spec.load_cell(ROOT, args.workload)
    if jax.devices()[0].platform == "cpu":
        print("control: needs the chip", file=sys.stderr)
        return 2
    cell_run.use_compile_cache(cell.bench_dir / ".jax_cache")
    compiles = cell_run.CompileCounter()
    ref = spec.reference(cell)
    arch = cell.config
    for seed in args.seeds:
        params = cell_run.build(cell, seed)
        served = cell_run.serve(cell, params, seed, args.seconds, False,
                                t_proc0=time.monotonic(), compiles=compiles)
        cell_run.report(served)
        sample = correct.sample(served.drive.finished_in_window(), seed)
        t = time.monotonic()
        program = correct.widest_gap(ref, params, arch, sample)
        t_ref = time.monotonic() - t
        control = correct.control_gap(ref, params, arch, sample)
        print(json.dumps({
            "cell": cell.name, "seed": seed, "program_gap": program,
            "control_gap": control, "requests": len(sample),
            "tokens": sum(len(r.req.tokens) for r in sample),
            "reference_s": t_ref}), flush=True)
        del params, served
    return 0


if __name__ == "__main__":
    sys.exit(main())
