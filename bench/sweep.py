"""Find the knee of an open-loop cell: its mix at several arrival rates.

    python bench/sweep.py --workload danube18b.chat --rates 0.2 0.3 0.4 \
        --seconds 30 --seed 5

One process, one set of weights; for each session rate the cell's own
set-up, warm phase and a window of ``--seconds``, then one JSON line: the
rate of requests due, TTFT p95 and median from the due time, the median
TTFT of the requests due in each half of the window, the queue wait's and
TPOT's p95, the lane occupancy, the tokens per second and the tick (each as
the cell's metric readers read it), the requests completed, and the
backlog: the requests outstanding (due and not yet answered, in the queue
or in a slot), at the window's open and close and as a mean over each half
of the window (read every 0.25 s from the records after the run).  A
backlog or a TTFT that grows through the window means the rate is past
what the system sustains: a rate is sustained when the second half's mean
backlog is at most 2 above the first half's, and the second half's median
TTFT is at most 1.5 times the first half's plus 1 s.  (The counts at the
open and the close alone are two instants, each off by the few requests in
service.)  Rates run in the order given, and the sweep stops at the first
rate that is not sustained; its last line names the knee, the highest
sustained rate, and that first rate above it.  The benchmark's own runs do
not run this; the chosen rate is written into the mix file.
"""
import argparse
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def outstanding(records, t: float) -> int:
    """Requests due by ``t`` and not answered by then."""
    return sum(1 for r in records if r.due <= t
               and (r.req is None or r.req.done_s is None
                    or r.req.done_s > t))


def backlog_halves(records, a: float, b: float, step: float = 0.25) -> list:
    """The mean of ``outstanding`` over each half of [a, b), read every
    ``step`` seconds."""
    mid = (a + b) / 2
    return [sum(outstanding(records, t) for t in ts) / len(ts)
            for ts in ([a + i * step for i in range(int((mid - a) / step))],
                       [mid + i * step for i in range(int((b - mid) / step))])]


def sustained(row: dict) -> bool:
    """The rule above, on one rate's line."""
    a, b = row["ttft_p50_halves_s"]
    first, second = row["backlog_halves"]
    return (second <= first + 2
            and a is not None and b is not None and b <= 1.5 * a + 1.0)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()

    import jax

    from bench.harness import cell_run, readers, spec
    from bench.harness.drive import censored, pct

    cell = spec.load_cell(ROOT, args.workload)
    if jax.devices()[0].platform == "cpu":
        print("sweep: needs the chip", file=sys.stderr)
        return 2
    cell_run.use_compile_cache(cell.bench_dir / ".jax_cache")
    compiles = cell_run.CompileCounter()
    params = cell_run.build(cell, args.seed)
    kind = jax.devices()[0].device_kind

    knee = above = None
    for rate in args.rates:
        traffic = json.loads(json.dumps(cell.traffic))
        traffic["sessions"]["rate_per_s"] = rate
        c = dataclasses.replace(cell, traffic=traffic)
        served = cell_run.serve(c, params, args.seed, args.seconds, False,
                                t_proc0=time.monotonic(), compiles=compiles)
        ctx = cell_run.context(c, served, kind)
        d = served.drive
        due = d.in_window()
        ttft = [censored(r.req.first_token_s if r.req is not None else None,
                         d.close_s) - r.due for r in due]
        recs = list(d.records.values())
        mid = (d.open_s + d.close_s) / 2
        halves = [[x for x, r in zip(ttft, due) if (r.due < mid) == first]
                  for first in (True, False)]
        row = {
            "rate_sessions_per_s": rate,
            "requests_due_per_s": len(due) / ctx.window_s,
            "ttft_p95_s": readers.ttft_p95(ctx),
            "ttft_p50_s": pct(ttft, 50) if ttft else None,
            "ttft_p50_halves_s": [pct(h, 50) if h else None for h in halves],
            "queue_wait_p95_s": readers.queue_wait_p95(ctx),
            "tpot_p95_s": readers.tpot_p95(ctx),
            "lane_occupancy": readers.lane_occupancy(ctx),
            "completed": len(d.finished_in_window()),
            "outstanding_open": outstanding(recs, d.open_s),
            "outstanding_close": outstanding(recs, d.close_s),
            "backlog_halves": backlog_halves(recs, d.open_s, d.close_s),
            "tokens_per_s": readers.tokens_per_s(ctx),
            "tick_ms": readers.tick_ms(ctx)}
        row["sustained"] = sustained(row)
        print(json.dumps(row), flush=True)
        if not row["sustained"]:
            above = rate
            break
        knee = rate
    print(json.dumps({"knee_sessions_per_s": knee,
                      "first_not_sustained": above}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
