"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Ordinal claims from the paper
are asserted inline (see each module's docstring for the claim list);
absolute magnitudes are host-scale, not RDMA-scale.

Run: PYTHONPATH=src python -m benchmarks.run [--only NAME]
"""
from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated substring filter on benchmark name")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from . import fastpath, kv_store, pipelines, roofline, serve

    benches = [
        ("table1_kv_latency", kv_store.bench_kv_latency),
        ("fig3_kv_throughput", kv_store.bench_kv_throughput),
        ("fig4_saturation", kv_store.bench_saturation),
        ("fig6_fastpath_breakdown", fastpath.bench_fastpath_breakdown),
        ("fig1_fig7_noop_pipeline", fastpath.bench_noop_pipeline),
        ("trie_ns_per_level", fastpath.bench_trie),
        ("fig10_smart_farming", pipelines.bench_farming),
        ("fig11_collision_detection", pipelines.bench_collision),
        ("serve_cluster_ttft_tpot", pipelines.bench_serve_cluster),
        ("serve_prefix_reuse", serve.bench_serve_prefix_reuse),
        ("serve_mixed_tick", serve.bench_serve_mixed_tick),
        ("serve_speculative", serve.bench_serve_speculative),
        ("serve_multi_model", serve.bench_serve_multi_model),
        ("serve_chaos", serve.bench_serve_chaos),
        ("serve_overload", serve.bench_serve_overload),
        ("serve_kv_quant", serve.bench_serve_kv_quant),
        ("serve_replica_scaling", serve.bench_serve_replica_scaling),
        ("roofline_table", lambda out: roofline.table(out)),
        ("roofline_kv_bytes", lambda out: roofline.kv_bytes_table(out)),
    ]

    def out(line: str) -> None:
        print(line, flush=True)

    failures = []
    only = args.only.split(",") if args.only else None
    for name, fn in benches:
        if only and not any(sub in name for sub in only):
            continue
        print(f"# === {name} ===", flush=True)
        t0 = time.time()
        try:
            fn(out)
            print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)
        except Exception as e:  # keep going; report at the end
            failures.append((name, repr(e)))
            print(f"# {name} FAILED: {e!r}", flush=True)
    if failures:
        print(f"# {len(failures)} benchmark(s) failed", file=sys.stderr)
        sys.exit(1)
    print("# ALL BENCHMARKS PASS")


if __name__ == "__main__":
    main()
