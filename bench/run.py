"""Run one cell of the benchmark once.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout that holds ``BENCHMARK.json``, this
directory and the program under ``src/``.  The cell, its configuration, its
traffic mix and its metric readers are found by name from
``BENCHMARK.json`` and the files beside this one.  The last line of standard
output is the result: ``correct``, ``attempted``, ``failed``, ``metrics``
(end-to-end with ``--trace 0``, per-layer with ``--trace 1``), ``device``,
``breakdown`` when traced, and ``checks``, each number compared with its
limit.  Earlier lines on standard error give the set-up split, the sample
counts, the generator's lateness, the compilations inside the window and
the peak memory; the last lines there repeat the checks.

Exits non-zero, printing no result, when JAX finds no accelerator or fewer
chips than the cell asks for.
"""
import time

T_PROC0 = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no libtpu logs in /tmp


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.harness import spec

    cell = spec.load_cell(ROOT, args.workload)
    import jax

    devs = jax.devices()
    if devs[0].platform == "cpu" or len(devs) < cell.chips:
        print(f"bench: cell {cell.name} needs {cell.chips} accelerator "
              f"chip(s); JAX found {len(devs)} {devs[0].platform} "
              f"device(s) ({devs[0].device_kind})", file=sys.stderr)
        return 2
    from bench.harness.cell_run import run_cell

    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      t_proc0=T_PROC0,
                      cache_dir=cell.bench_dir / ".jax_cache")
    print(json.dumps(result, allow_nan=False), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
