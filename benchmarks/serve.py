"""Serving-path benchmarks beyond the paper's figures: the unified
token-budget tick on the paged KV / prefix-reuse fast path.

``serve_prefix_reuse``: multi-turn chat sessions over FIFO affinity — every
turn's prompt extends the session's full history, so with the per-replica
prefix trie each warm turn prefills only the suffix past the last cached
block.  The jitted mixed step is warmed up BEFORE timing and the compile
time is reported as its own field, so TTFT percentiles measure steady state
instead of XLA compiles (the step's packed shape is fixed, so there is
exactly ONE compile to exclude).  Reports TTFT p50/p99 per turn round, the
token-level prefix hit rate, and the skipped-block count; asserts the
fast-path invariants (one device→host sync per tick, ``host_syncs ==
ticks``; warm turns reuse > 0 tokens and prefill strictly fewer than they
carry).

``serve_mixed_tick``: long prefills injected into an ACTIVE decode pool.
With a bounded token budget the prompt spreads over budget-sized chunks that
ride in each tick's remainder, so decoding sessions keep emitting one token
per tick and the inter-token stall is bounded by the chunk budget.  The
baseline is the SAME engine with a monolithic budget (the whole prompt packs
into one tick) — i.e. the head-of-line behavior of the old phase-separated
tick, where a long prefill takes the tick hostage.  Reports decode TPOT
p50/p99 over the contention window for both; the chunked p99 must beat the
monolithic p99 (asserted outside smoke mode).

``serve_multi_model``: one ``ServeNode`` hosting a paged attention LIGHT
model and a dense SSM HEAVY model side by side, with a ``CascadeRoute``
between them, driven into overload.  The cascade gate's logprob threshold is
CALIBRATED (median of light-model mean logprobs over probe requests) so the
escalation rate is a property of the gate, not a lucky constant.  Records
the escalation rate at the gate, shed/redirect counts once the light tier's
per-replica queues hit the watermark (MultiTASC++-style bounded admission),
and p50/p99 TTFT/TPOT per deployment; asserts each deployment's own
host-sync discipline (paged: ``host_syncs == ticks``; dense SSM:
``host_syncs == decode_ticks + prefill_batches``) and that every request is
answered — shed at the light tier fails over to the heavy tier, never into
silence.

``serve_speculative``: decode TPOT with speculative decoding on the unified
tick.  Three passes over identical prompts at the SAME token budget (the
step's packed shape — and so its per-dispatch cost — is fixed either way):
a non-speculative baseline; a speculative pass whose requests carry the
baseline's own output as drafts (the self-drafting cascade's perfect-
drafter limit — exactly what a ``CascadeRoute`` plants on escalation when
light and heavy agree); and a speculative pass drafting only from the
request's own history (n-gram prompt lookup).  Records decode TPOT p50/p99,
acceptance rate, and the drafted/accepted/rolled-back counters.  Asserts —
always — that greedy outputs are IDENTICAL across all three passes
(rejection sampling is lossless), that accepted <= drafted with
rolled-back making up the difference, that the perfect-drafter acceptance
rate is >= 0.5, and ``host_syncs == ticks`` with speculation on; outside
smoke mode the speculative TPOT p50 must beat the baseline (one sync
amortized over multiple accepted tokens).

``serve_overload``: a diurnal-style overload — a batch flood parks on a
single watermarked replica, then an interactive trickle arrives on top.
Pass A is the shed-only FIFO baseline (no SLO classes, no preemption): the
trickle either sheds at the watermark or queues behind the flood.  Pass B
runs the same workload with SLO classes and ``preempt=True``: over-watermark
interactive arrivals admit via preempt-before-shed, and the engine's EDF
preemption spills a batch victim's KV to the host-side pool to issue them
immediately.  Asserts — always, including smoke — that B's interactive p99
TTFT beats A's, that B serves at least as many interactive requests, that
every batch request in B still completes (EDF aging: absolute virtual
deadlines bound starvation), zero stranded requests in both passes, and the
sync discipline per pass (A strict ``host_syncs == ticks``; B ``host_syncs
== ticks + spill_syncs``).

Set ``BENCH_SMOKE=1`` for a tiny-config, few-tick variant of all of these
(CI runs this on every PR).  Results land in BENCH_serve.json so the
serving perf trajectory is tracked across PRs.
"""
from __future__ import annotations

import json
import math
import os
import time

import jax
import numpy as np

BENCH_JSON = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                          "BENCH_serve.json")


def _smoke() -> bool:
    return os.environ.get("BENCH_SMOKE", "") == "1"


def _write_results(key: str, results: dict, out) -> None:
    """Merge one benchmark's results into BENCH_serve.json."""
    data = {}
    if os.path.exists(BENCH_JSON):
        try:
            with open(BENCH_JSON) as f:
                data = json.load(f)
        except (OSError, json.JSONDecodeError):
            data = {}
    if not all(isinstance(v, dict) and ("turns" in v or "chunked" in v
                                        or "total" in v or "route" in v
                                        or "baseline" in v or "faults" in v)
               for v in data.values()):
        data = {}                     # pre-PR3 flat schema: start fresh
    data[key] = results
    with open(BENCH_JSON, "w") as f:
        json.dump(data, f, indent=2, sort_keys=True)
    out(f"# wrote {BENCH_JSON}[{key}]")


def _pct(xs, q):
    xs = sorted(xs)
    return xs[min(len(xs) - 1, int(q * len(xs)))] if xs else float("nan")


def bench_serve_prefix_reuse(out) -> dict:
    from repro.core.pools import DispatchPolicy
    from repro.models import init_params
    from repro.models.config import ModelConfig
    from repro.serving.cluster import ServeCluster
    from repro.serving.engine import EngineStats

    cfg = ModelConfig(name="bench", family="dense", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
                      dtype="float32", q_chunk=16)
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)

    n_sessions, n_turns = (2, 2) if _smoke() else (6, 4)
    block_size = 16
    new_tokens_per_turn, decode_budget = 24, 8
    results: dict = {"turns": []}

    with ServeCluster(cfg, params, n_replicas=2, n_slots=4, max_len=256,
                      policy=DispatchPolicy.FIFO, block_size=block_size) as c:
        # Warm up the ONE fixed-shape jitted mixed step before timing, then
        # reset stats: TTFT percentiles below are steady state and the
        # compile cost is its own field.
        t0 = time.monotonic()
        c.submit("warmup", "w0", rng.integers(0, cfg.vocab_size,
                                              (8,)).astype(np.int32),
                 max_new_tokens=2)
        c.run_until_drained()
        compile_s = time.monotonic() - t0
        for e in c.engines:
            e.stats = EngineStats()
        results["compile_s"] = compile_s
        out(f"serve_prefix_reuse/compile,{compile_s*1e6:.1f},"
            f"one_time_jit_cost")

        history = {f"s{i}": rng.integers(0, cfg.vocab_size,
                                         (new_tokens_per_turn,)).astype(np.int32)
                   for i in range(n_sessions)}
        prev_hits = 0
        for turn in range(n_turns):
            marks = {e: (len(e.stats.ttft_s),
                         e.stats.prefix_hit_tokens, e.stats.prompt_tokens)
                     for e in c.engines}
            t0 = time.monotonic()
            for sess, hist in history.items():
                c.submit(sess, f"{sess}-t{turn}", hist,
                         max_new_tokens=decode_budget)
            c.run_until_drained()
            dt = time.monotonic() - t0
            ttft = sorted(t for e in c.engines
                          for t in e.stats.ttft_s[marks[e][0]:])
            hit = sum(e.stats.prefix_hit_tokens - marks[e][1]
                      for e in c.engines)
            prompt = sum(e.stats.prompt_tokens - marks[e][2]
                         for e in c.engines)
            row = {
                "turn": turn,
                "ttft_p50_us": _pct(ttft, 0.50) * 1e6,
                "ttft_p99_us": _pct(ttft, 0.99) * 1e6,
                "prompt_tokens": prompt,
                "prefix_hit_tokens": hit,
                "hit_rate": hit / max(1, prompt),
                "skipped_blocks": hit // block_size,
                "wall_s": dt,
            }
            results["turns"].append(row)
            out(f"serve_prefix_reuse/turn{turn},{row['ttft_p50_us']:.1f},"
                f"ttft_p99_us={row['ttft_p99_us']:.1f} "
                f"hit_rate={row['hit_rate']:.2f} "
                f"skipped_blocks={row['skipped_blocks']}")
            if turn > 0:
                assert hit > prev_hits or hit > 0, \
                    "warm turns must reuse cached prefix blocks"
            prev_hits = hit
            # next turn: history grows by this turn's output + new user text
            for sess in history:
                res = c.result(f"{sess}-t{turn}")
                assert res is not None
                history[sess] = np.concatenate(
                    [history[sess], np.asarray(res, np.int32),
                     rng.integers(0, cfg.vocab_size,
                                  (new_tokens_per_turn,)).astype(np.int32)])

        st = c.stats()
        assert st["host_syncs"] == st["ticks"], \
            "a unified tick made more than one device→host transfer"
        assert st["prefix_hit_tokens"] > 0, "no prefix reuse over warm turns"
        # strictly fewer prefill FLOPs than a cache-less engine would spend
        assert st["prefill_tokens"] < st["prompt_tokens"]
        results["total"] = {
            "requests": st["requests"],
            "prompt_tokens": st["prompt_tokens"],
            "prefill_tokens": st["prefill_tokens"],
            "prefix_hit_tokens": st["prefix_hit_tokens"],
            "hit_rate": st["prefix_hit_tokens"] / max(1, st["prompt_tokens"]),
            "ttft_p50_us": st["ttft_p50_s"] * 1e6,
            "ttft_p99_us": st["ttft_p99_s"] * 1e6,
            "blocks_in_use": st["blocks_in_use"],
            "ticks": st["ticks"],
        }
    out(f"serve_prefix_reuse/total,{results['total']['ttft_p50_us']:.1f},"
        f"hit_rate={results['total']['hit_rate']:.2f} "
        f"prefill_tokens={results['total']['prefill_tokens']} "
        f"of_prompt_tokens={results['total']['prompt_tokens']}")
    out("serve_prefix_reuse/CLAIM warm-turns-skip-prefix-prefill,PASS,exact")
    out("serve_prefix_reuse/CLAIM steady-state-ttft-excludes-compile,PASS,exact")
    _write_results("serve_prefix_reuse", results, out)
    return results


def bench_serve_mixed_tick(out) -> dict:
    from repro.models import init_params
    from repro.models.config import ModelConfig
    from repro.serving.engine import ServeEngine
    from repro.serving.scheduler import Request

    smoke = _smoke()
    cfg = ModelConfig(name="bench-mixed", family="dense", n_layers=2,
                      d_model=64 if smoke else 256, n_heads=4, n_kv_heads=2,
                      d_ff=128 if smoke else 512, vocab_size=256,
                      dtype="float32", q_chunk=32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    n_slots = 8
    long_S = 96 if smoke else 384
    max_len = 160 if smoke else 512
    decode_new = 16 if smoke else 48
    chunk_budget = 32 if smoke else 48
    budgets = {"chunked": chunk_budget, "monolithic": long_S + n_slots}
    results: dict = {}

    for label, budget in budgets.items():
        rng = np.random.default_rng(7)
        eng = ServeEngine(cfg, params, n_slots=n_slots, max_len=max_len,
                          paged=True, block_size=16, token_budget=budget)
        done = []
        eng.on_complete = done.append
        mk = lambda rid, S, n: Request(
            request_id=rid, session_key=rid,
            prompt=rng.integers(0, cfg.vocab_size, (S,)).astype(np.int32),
            max_new_tokens=n)
        # warm the (fixed-shape, compiles-once) step outside the timings
        t0 = time.monotonic()
        eng.submit(mk("warm", 8, 2))
        eng.run_until_drained()
        compile_s = time.monotonic() - t0
        # steady decode pool: six chat sessions mid-generation
        for i in range(6):
            eng.submit(mk(f"chat{i}", 8, decode_new))
        for _ in range(4):
            eng.tick()
        mark = len(eng.stats.tpot_s)
        # inject two long prefills into the busy pool
        for i in range(2):
            eng.submit(mk(f"wall{i}", long_S, 4))
        t0 = time.monotonic()
        eng.run_until_drained()
        wall_s = time.monotonic() - t0
        tpot = eng.stats.tpot_s[mark:]
        assert eng.stats.host_syncs == eng.stats.ticks
        walls = [r for r in done if r.request_id.startswith("wall")]
        assert len(walls) == 2 and all(r.error is None for r in done)
        row = {
            "token_budget": budget,
            "compile_s": compile_s,
            "tpot_p50_us": _pct(tpot, 0.50) * 1e6,
            "tpot_p99_us": _pct(tpot, 0.99) * 1e6,
            "wall_ttft_p99_us": _pct(
                [r.first_token_s - r.arrived_s for r in walls], 0.99) * 1e6,
            "prefill_chunks": eng.stats.prefill_chunks,
            "ticks": eng.stats.ticks,
            "wall_s": wall_s,
        }
        results[label] = row
        out(f"serve_mixed_tick/{label},{row['tpot_p50_us']:.1f},"
            f"tpot_p99_us={row['tpot_p99_us']:.1f} "
            f"prefill_chunks={row['prefill_chunks']} ticks={row['ticks']}")

    ratio = (results["monolithic"]["tpot_p99_us"]
             / max(1e-9, results["chunked"]["tpot_p99_us"]))
    results["stall_ratio_p99"] = ratio
    out(f"serve_mixed_tick/stall_ratio,{ratio:.2f},"
        f"monolithic_p99_over_chunked_p99")
    if not smoke:
        # the tentpole claim: bounding the chunk budget bounds the
        # inter-token stall a concurrent long prefill can inflict
        assert results["chunked"]["tpot_p99_us"] \
            < results["monolithic"]["tpot_p99_us"], \
            "chunked prefill must bound decode TPOT below the monolithic tick"
        out("serve_mixed_tick/CLAIM chunked-tpot-beats-monolithic,PASS,exact")
    _write_results("serve_mixed_tick", results, out)
    return results


def bench_serve_speculative(out) -> dict:
    from repro.models import init_params
    from repro.models.config import ModelConfig
    from repro.serving.engine import ServeEngine
    from repro.serving.scheduler import Request

    smoke = _smoke()
    cfg = ModelConfig(name="bench-spec", family="dense", n_layers=2,
                      d_model=64 if smoke else 256, n_heads=4, n_kv_heads=2,
                      d_ff=128 if smoke else 512, vocab_size=256,
                      dtype="float32", q_chunk=32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    n_slots = 4 if smoke else 8
    S = 16 if smoke else 32
    decode_new = 16 if smoke else 48
    spec_k = 4
    # one budget for every pass: full drafting headroom, fixed packed shape
    # (so baseline and speculative ticks dispatch the same program cost and
    # the TPOT delta is pure accepted-token amortization, not shape luck)
    budget = n_slots * (1 + spec_k) + 8
    max_len = 96 if smoke else 160
    results: dict = {}

    def run(label, spec, drafts=None):
        rng = np.random.default_rng(11)      # same stream ⇒ same prompts
        eng = ServeEngine(cfg, params, n_slots=n_slots, max_len=max_len,
                          paged=True, block_size=16, token_budget=budget,
                          spec_k=spec)
        done = []
        eng.on_complete = done.append
        t0 = time.monotonic()
        eng.submit(Request(
            request_id="warm", session_key="w", max_new_tokens=2,
            prompt=rng.integers(0, cfg.vocab_size, (8,)).astype(np.int32)))
        eng.run_until_drained()
        compile_s = time.monotonic() - t0
        mark = len(eng.stats.tpot_s)
        for i in range(n_slots):
            req = Request(
                request_id=f"chat{i}", session_key=f"s{i}",
                prompt=rng.integers(0, cfg.vocab_size,
                                    (S,)).astype(np.int32),
                max_new_tokens=decode_new)
            if drafts is not None:
                req.draft_tokens = np.asarray(drafts[req.request_id],
                                              np.int32)
            eng.submit(req)
        t0 = time.monotonic()
        eng.run_until_drained()
        wall_s = time.monotonic() - t0
        assert eng.stats.host_syncs == eng.stats.ticks, \
            "speculation broke the one-sync-per-tick invariant"
        st = eng.stats
        assert st.spec_accepted <= st.spec_drafted
        assert st.spec_accepted + st.spec_rolled_back == st.spec_drafted
        tpot = st.tpot_s[mark:]
        row = {
            "spec_k": spec, "token_budget": budget, "compile_s": compile_s,
            "tpot_p50_us": _pct(tpot, 0.50) * 1e6,
            "tpot_p99_us": _pct(tpot, 0.99) * 1e6,
            "ticks": st.ticks, "tokens_out": st.tokens_out,
            "drafted": st.spec_drafted, "accepted": st.spec_accepted,
            "rolled_back": st.spec_rolled_back,
            "acceptance_rate": st.spec_acceptance_rate(),
            "wall_s": wall_s,
        }
        results[label] = row
        out(f"serve_speculative/{label},{row['tpot_p50_us']:.1f},"
            f"tpot_p99_us={row['tpot_p99_us']:.1f} ticks={row['ticks']} "
            f"drafted={row['drafted']} accepted={row['accepted']} "
            f"rolled_back={row['rolled_back']} "
            f"acceptance_rate={row['acceptance_rate']:.2f}")
        return {r.request_id: list(r.tokens) for r in done
                if r.request_id.startswith("chat")}

    base_toks = run("baseline", 0)
    # the self-drafting cascade's perfect-drafter limit: requests carry the
    # target's own greedy output as their draft stream (what CascadeRoute
    # plants on escalation when light and heavy agree)
    spec_toks = run("speculative", spec_k, drafts=base_toks)
    ngram_toks = run("self_drafting", spec_k)
    # losslessness: greedy streams identical across all three passes
    assert spec_toks == base_toks, \
        "speculative greedy output diverged from the baseline"
    assert ngram_toks == base_toks, \
        "self-drafting greedy output diverged from the baseline"
    sp = results["speculative"]
    assert sp["drafted"] > 0 and sp["acceptance_rate"] >= 0.5, \
        "perfect drafts must verify at >= 0.5 acceptance"
    speedup = (results["baseline"]["tpot_p50_us"]
               / max(1e-9, sp["tpot_p50_us"]))
    results["tpot_p50_speedup"] = speedup
    out(f"serve_speculative/speedup,{speedup:.2f},"
        f"baseline_p50_over_speculative_p50 "
        f"ngram_acceptance={results['self_drafting']['acceptance_rate']:.2f}")
    if not _smoke():
        assert sp["tpot_p50_us"] < results["baseline"]["tpot_p50_us"], \
            "speculative decode must beat baseline TPOT p50"
        out("serve_speculative/CLAIM spec-tpot-beats-baseline,PASS,exact")
    out("serve_speculative/CLAIM greedy-output-lossless,PASS,exact")
    out("serve_speculative/CLAIM counters-consistent,PASS,exact")
    _write_results("serve_speculative", results, out)
    return results


def bench_serve_multi_model(out) -> dict:
    import statistics

    from repro.core.pools import DispatchPolicy
    from repro.models import init_params
    from repro.models.config import ModelConfig
    from repro.serving.cluster import CascadeGate, CascadeRoute, ServeNode
    from repro.serving.engine import EngineStats

    smoke = _smoke()
    light_cfg = ModelConfig(name="light", family="dense", n_layers=2,
                            d_model=32 if smoke else 64, n_heads=4,
                            n_kv_heads=2, d_ff=64 if smoke else 128,
                            vocab_size=256, dtype="float32", q_chunk=16)
    # dense SSM heavy model: d_inner = 2*d_model must divide ssm_head_dim 64
    heavy_cfg = ModelConfig(name="heavy", family="ssm", n_layers=2,
                            d_model=64 if smoke else 128, n_heads=4,
                            n_kv_heads=2, d_ff=128 if smoke else 256,
                            vocab_size=256, dtype="float32")
    lp = init_params(jax.random.PRNGKey(0), light_cfg)
    hp = init_params(jax.random.PRNGKey(1), heavy_cfg)
    rng = np.random.default_rng(0)

    S = 12 if smoke else 24                  # ONE prompt length: the dense
    max_new = 4 if smoke else 8              # prefill compiles stay bounded
    n_requests = 10 if smoke else 32
    n_sessions = 4
    # depth counts decoding rows too (they gate a new arrival's wait just
    # as queued ones do), so the watermark must leave room above n_slots:
    # 4 in service + 4 waiting per replica, anything beyond redirects/sheds
    watermark = 3 if smoke else 8
    prompt = lambda: rng.integers(0, 256, (S,)).astype(np.int32)
    results: dict = {}

    with ServeNode(n_workers=2) as node:
        light = node.deploy("light", light_cfg, lp, n_replicas=2, n_slots=4,
                            max_len=96, policy=DispatchPolicy.FIFO,
                            watermark=None)      # opened up for calibration
        heavy = node.deploy("heavy", heavy_cfg, hp, n_replicas=2, n_slots=4,
                            max_len=96)          # unbounded: the spillway

        # ---- warm both programs out of the timings (light: the ONE mixed
        # step; heavy: dense prefill for group sizes 1 and 2 + decode step)
        t0 = time.monotonic()
        light.submit("warm", "lw0", prompt(), max_new_tokens=2)
        for i in range(3):
            heavy.submit("warm", f"hw{i}", prompt(), max_new_tokens=2)
        node.run_until_drained()
        results["compile_s"] = time.monotonic() - t0

        # ---- calibrate the gate: median mean-logprob of light generations
        # over probe requests → escalation rate is a property of the GATE
        probe_scores: list[float] = []
        probe = lambda req: probe_scores.append(req.mean_logprob())
        light.on_done.append(probe)
        for i in range(8):
            light.submit(f"cal{i % n_sessions}", f"cal{i}", prompt(),
                         max_new_tokens=max_new)
        node.run_until_drained()
        light.on_done.remove(probe)
        threshold = statistics.median(probe_scores)
        gate = CascadeGate("logprob", threshold=threshold)
        route = CascadeRoute(light, heavy, gate)
        out(f"serve_multi_model/gate,{threshold:.4f},"
            f"median_mean_logprob_over_{len(probe_scores)}_probes")

        # ---- measured overload phase: arrivals outpace service (two
        # requests per driver step, vs a service rate of n_slots/max_new
        # requests per tick per replica), so queues climb to the watermark
        # and stay there — some requests serve and face the gate, the
        # over-watermark tail sheds or redirects (the MultiTASC++ regime,
        # not a one-shot burst that sheds everything)
        for eng in light.engines + heavy.engines:
            eng.stats = EngineStats()
        light.watermark = watermark
        rids = [f"r{i}" for i in range(n_requests)]
        t0 = time.monotonic()
        for i, rid in enumerate(rids):
            route.submit(f"s{i % n_sessions}", rid, prompt(),
                         max_new_tokens=max_new)
            if i % 2 == 1:
                node.step()
        node.run_until_drained()
        wall_s = time.monotonic() - t0

        ls, hs, rs = light.stats(), heavy.stats(), route.stats()
        # each deployment upholds ITS OWN fast-path discipline
        assert ls["host_syncs"] == ls["ticks"], \
            "paged light deployment broke host_syncs == ticks"
        assert hs["host_syncs"] == hs["decode_ticks"] + hs["prefill_batches"], \
            "dense SSM heavy deployment broke the phase-separated discipline"
        # bounded admission really engaged under the burst
        assert ls["shed"] + ls["redirected"] > 0, \
            "overload burst never hit the light tier's watermark"
        assert rs["escalated"] > 0, "nothing escalated under overload"
        # no request vanishes: shed at light fails over to heavy
        for rid in rids:
            res = route.result(rid)
            assert res is not None and len(res) == max_new, \
                f"{rid} unanswered: {route.error(rid)!r}"
        if not smoke:
            assert rs["gate_trips"] > 0, "calibrated gate never tripped"
            assert rs["escalation_rate"] < 1.0, \
                "median-calibrated gate escalated everything"

        def dep_row(st):
            return {
                "requests": st["requests"], "shed": st["shed"],
                "redirected": st["redirected"],
                "tokens_out": st["tokens_out"],
                "ttft_p50_us": st["ttft_p50_s"] * 1e6,
                "ttft_p99_us": st["ttft_p99_s"] * 1e6,
                "tpot_p50_us": st["tpot_p50_s"] * 1e6,
                "tpot_p99_us": st["tpot_p99_s"] * 1e6,
            }

        results["route"] = {
            "requests": rs["requests"], "escalated": rs["escalated"],
            "gate_trips": rs["gate_trips"],
            "error_failovers": rs["error_failovers"],
            "escalation_rate": rs["escalation_rate"],
            "threshold": threshold,
        }
        results["light"] = dep_row(ls)
        results["heavy"] = dep_row(hs)
        results["total"] = {"requests": n_requests, "wall_s": wall_s,
                            "watermark": watermark}
        for name, row in (("light", results["light"]),
                          ("heavy", results["heavy"])):
            out(f"serve_multi_model/{name},{row['ttft_p50_us']:.1f},"
                f"ttft_p99_us={row['ttft_p99_us']:.1f} "
                f"tpot_p50_us={row['tpot_p50_us']:.1f} "
                f"shed={row['shed']} redirected={row['redirected']}")
        out(f"serve_multi_model/route,{rs['escalation_rate']:.2f},"
            f"escalated={rs['escalated']}_of_{rs['requests']} "
            f"gate_trips={rs['gate_trips']} "
            f"error_failovers={rs['error_failovers']}")
    out("serve_multi_model/CLAIM per-deployment-sync-invariants,PASS,exact")
    out("serve_multi_model/CLAIM overload-sheds-or-redirects,PASS,exact")
    out("serve_multi_model/CLAIM shed-fails-over-never-drops,PASS,exact")
    _write_results("serve_multi_model", results, out)
    return results


def bench_serve_chaos(out) -> dict:
    """Chaos smoke: a SEEDED fault schedule (replica crash with KV
    migration, transient submit errors, slow ticks) over a cascade-style
    serve setup.  The claim is availability, not speed: every request
    reaches a terminal state — a served result or a structured error —
    with zero stranded requests, and the drain resolves rather than
    timing out.  Failover counters and post-fault latency land in
    BENCH_serve.json so degraded-mode tails are tracked across PRs."""
    from repro.models import init_params
    from repro.models.config import ModelConfig
    from repro.serving.cluster import ServeNode
    from repro.serving.faults import FaultInjector, FaultKind, FaultSpec

    smoke = _smoke()
    cfg = ModelConfig(name="light", family="dense", n_layers=2,
                      d_model=32 if smoke else 64, n_heads=4, n_kv_heads=2,
                      d_ff=64 if smoke else 128, vocab_size=256,
                      dtype="float32", q_chunk=16)
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    S = 12 if smoke else 24
    max_new = 4 if smoke else 8
    n_requests = 12 if smoke else 48
    prompt = lambda: rng.integers(0, 256, (S,)).astype(np.int32)
    results: dict = {}

    injector = FaultInjector([
        # one replica dies at a seeded tick; its sessions migrate (KV
        # spill/restore) or replay onto the sibling
        FaultSpec(FaultKind.CRASH, deployment="light", at_tick=-8,
                  kv_recoverable=True),
        # a couple of transient submit failures bounce to the retry path
        FaultSpec(FaultKind.SUBMIT_ERROR, deployment="light", count=2),
        # and some slow ticks stretch the tail without tripping the watchdog
        FaultSpec(FaultKind.SLOW_TICK, deployment="light", at_tick=2,
                  count=3, duration_s=0.002),
    ], seed=1234)

    with ServeNode(n_workers=2) as node:
        dep = node.deploy("light", cfg, params, n_replicas=2, n_slots=4,
                          max_len=96, watchdog_s=1.0)
        # warm the mixed program out of the measurement
        t0 = time.monotonic()
        dep.submit("warm", "w0", prompt(), max_new_tokens=2)
        node.run_until_drained()
        results["compile_s"] = time.monotonic() - t0

        node.install_faults(injector)
        rids = [f"r{i}" for i in range(n_requests)]
        t0 = time.monotonic()
        for i, rid in enumerate(rids):
            dep.submit(f"s{i % 4}", rid, prompt(), max_new_tokens=max_new)
            if i % 3 == 2:
                node.step()
        node.run_until_drained()
        wall_s = time.monotonic() - t0

        st = dep.stats()
        stranded = [rid for rid in rids if dep.result(rid) is None]
        assert not stranded, f"stranded requests under chaos: {stranded}"
        errored = sum(1 for rid in rids if dep.error(rid) is not None)
        for rid in rids:
            err = dep.error(rid)
            if err is None:
                assert len(dep.result(rid)) == max_new
            else:
                assert isinstance(err, dict) and "error" in err, \
                    f"unstructured failure for {rid}: {err!r}"
        assert any(e.startswith("crash:") for e in injector.fired_log), \
            "seeded crash never fired"
        assert st["failovers"] >= 1, "crash did not mark the replica down"

        results["faults"] = {
            "failovers": st["failovers"],
            "rehomed": st["rehomed"], "migrated": st["migrated"],
            "replayed": st["replayed"],
            "failover_failed": st["failover_failed"],
            "submit_retries": st["submit_retries"],
            "spill_syncs": st["spill_syncs"],
            "fired": list(injector.fired_log),
        }
        results["total"] = {
            "requests": n_requests, "errored": errored, "wall_s": wall_s,
            "ttft_p99_us": st["ttft_p99_s"] * 1e6,
            "tpot_p99_us": st["tpot_p99_s"] * 1e6,
        }
        out(f"serve_chaos/failover,{st['failovers']},"
            f"rehomed={st['rehomed']} migrated={st['migrated']} "
            f"replayed={st['replayed']} retries={st['submit_retries']}")
        out(f"serve_chaos/total,{wall_s*1e6/n_requests:.1f},"
            f"requests={n_requests} errored={errored} "
            f"ttft_p99_us={results['total']['ttft_p99_us']:.1f}")
    out("serve_chaos/CLAIM zero-stranded-requests-under-chaos,PASS,exact")
    out("serve_chaos/CLAIM structured-errors-only,PASS,exact")
    _write_results("serve_chaos", results, out)
    return results


def bench_serve_overload(out) -> dict:
    """Overload A/B: shed-only FIFO vs SLO classes + KV preemption.

    One replica, watermarked queue, batch flood + interactive trickle.
    The baseline pass submits everything classless (EDF over a uniform
    class IS arrival-order FIFO) with preemption off — the pre-SLO
    behavior: interactive arrivals shed at the watermark or queue behind
    the flood.  The preempt pass tags the trickle ``interactive``: the
    door admits it over the watermark (preempt-before-shed) and the
    engine spills a batch victim to issue it at once."""
    from repro.models import init_params
    from repro.models.config import ModelConfig
    from repro.serving.cluster import ServeNode
    from repro.serving.scheduler import SLO_INTERACTIVE

    smoke = _smoke()
    cfg = ModelConfig(name="ovl", family="dense", n_layers=2,
                      d_model=32 if smoke else 64, n_heads=4, n_kv_heads=2,
                      d_ff=64 if smoke else 128, vocab_size=256,
                      dtype="float32", q_chunk=16)
    params = init_params(jax.random.PRNGKey(0), cfg)
    n_slots = 2
    n_batch = 6
    watermark = n_batch              # the parked flood sits AT the mark
    n_inter = 4 if smoke else 8
    batch_S, batch_new = (8, 8) if smoke else (16, 16)
    inter_S, inter_new = 4, 3
    results: dict = {}

    def run(label, *, preempt):
        rng = np.random.default_rng(3)
        done: dict[str, tuple[float | None, bool]] = {}

        def probe(req):
            ttft = (None if req.first_token_s is None
                    else req.first_token_s - req.arrived_s)
            done[req.request_id] = (ttft, req.error is None)

        with ServeNode(n_workers=1) as node:
            dep = node.deploy("ovl", cfg, params, n_replicas=1,
                              n_slots=n_slots, max_len=96,
                              prefix_cache=False, watermark=None,
                              preempt=preempt)
            # warm the one jitted step outside the measurement
            t0 = time.monotonic()
            dep.submit("warm", "w0",
                       rng.integers(0, 256, (batch_S,)).astype(np.int32),
                       max_new_tokens=2)
            node.run_until_drained()
            compile_s = time.monotonic() - t0
            dep.on_done.append(probe)

            rids = []
            for i in range(n_batch):        # the flood: fills both slots
                rid = f"b{i}"               # and parks the rest in queue
                rids.append(rid)
                dep.submit(f"bs{i}", rid,
                           rng.integers(0, 256,
                                        (batch_S,)).astype(np.int32),
                           max_new_tokens=batch_new)
            # arm the watermark only once the flood is INSIDE the engine
            # (upcall lambdas drained): the flood is accepted work parked
            # at the mark, and the watermark governs what arrives ON TOP —
            # the trickle.  Arming early would race the flood's own
            # admission lambdas and shed the flood at its own door.
            stop = time.monotonic() + 10
            while dep.engines[0].backlog() < n_batch:
                assert time.monotonic() < stop, "flood never reached engine"
                node.step()
                time.sleep(0.001)
            dep.watermark = watermark
            for j in range(n_inter):        # the trickle, on top of it
                rid = f"i{j}"
                rids.append(rid)
                dep.submit(f"is{j}", rid,
                           rng.integers(0, 256,
                                        (inter_S,)).astype(np.int32),
                           max_new_tokens=inter_new,
                           slo=SLO_INTERACTIVE if preempt else None)
                for _ in range(2):
                    node.step()
            node.run_until_drained()

            stranded = [r for r in rids
                        if dep.result(r) is None and dep.error(r) is None]
            assert not stranded, f"stranded under overload: {stranded}"
            # both passes: the accepted flood must complete — in the
            # preempt pass this is the EDF aging bound in action (the
            # preempted flood still finishes, nothing starves)
            berr = {f"b{i}": dep.error(f"b{i}") for i in range(n_batch)
                    if dep.error(f"b{i}") is not None}
            assert not berr, f"batch flood starved/refused: {berr}"
            st = dep.stats()
            if preempt:
                assert st["host_syncs"] == st["ticks"] + st["spill_syncs"]
                # every batch request issued (the classless warm request
                # rides in the batch histogram too, hence >=)
                assert st["queue_wait_s"].get("batch",
                                              {}).get("n", 0) >= n_batch
            else:
                assert st["spill_syncs"] == 0
                assert st["host_syncs"] == st["ticks"]

        inter_ttft = sorted(done[f"i{j}"][0] for j in range(n_inter)
                            if done.get(f"i{j}", (None, False))[1])
        # effective TTFT: a shed request never produced a token — its
        # first-token latency is unbounded, and the A/B claim must charge
        # the shed-only baseline for it rather than sampling the survivors
        eff_ttft = sorted((done[f"i{j}"][0]
                           if done.get(f"i{j}", (None, False))[1]
                           else float("inf")) for j in range(n_inter))
        row = {
            "compile_s": compile_s,
            "preempt": preempt,
            "interactive_served": len(inter_ttft),
            "interactive_shed": n_inter - len(inter_ttft),
            "interactive_ttft_p50_us": _pct(inter_ttft, 0.50) * 1e6,
            "interactive_ttft_p99_us": _pct(inter_ttft, 0.99) * 1e6,
            "batch_served": sum(1 for i in range(n_batch)
                                if done.get(f"b{i}", (None, False))[1]),
            "preemptions": st["preemptions"],
            "resumes": st["resumes"],
            "spilled_blocks": st["spilled_blocks"],
            "preempt_admits": st["preempt_admits"],
            "shed": st["shed"],
            "ticks": st["ticks"],
            "queue_wait_s": st["queue_wait_s"],
        }
        results[label] = row
        out(f"serve_overload/{label},{row['interactive_ttft_p50_us']:.1f},"
            f"ttft_p99_us={row['interactive_ttft_p99_us']:.1f} "
            f"served={row['interactive_served']}_of_{n_inter} "
            f"shed={row['shed']} preemptions={row['preemptions']} "
            f"resumes={row['resumes']}")
        return row, eff_ttft

    base, base_eff = run("baseline", preempt=False)
    pre, pre_eff = run("preempt", preempt=True)

    b_p99 = _pct(base_eff, 0.99) * 1e6       # inf when any shed landed p99
    p_p99 = _pct(pre_eff, 0.99) * 1e6
    assert pre["interactive_served"] == n_inter, \
        "preempt pass shed interactive work it should have admitted"
    assert pre["interactive_served"] >= base["interactive_served"]
    assert base["shed"] >= 1, \
        "the flood never pushed the baseline into its shed-only regime"
    assert pre["preemptions"] >= 1, "overload never triggered a preemption"
    assert p_p99 < b_p99, \
        "preemption failed to beat the shed-only FIFO interactive p99 TTFT"
    # EDF class separation inside the preempt pass: interactive queue wait
    # must sit well below the preempted batch flood's
    pw = pre["queue_wait_s"]
    assert pw["interactive"]["p50_s"] < pw["batch"]["p50_s"], \
        "interactive queue wait did not separate from the batch flood"
    results["total"] = {
        "n_batch": n_batch, "n_interactive": n_inter,
        "watermark": watermark,
        "baseline_eff_p99_finite": math.isfinite(b_p99),
        "preempt_eff_ttft_p99_us": p_p99,
    }
    out(f"serve_overload/effective_p99,{p_p99:.1f},"
        f"baseline_eff_p99_us={b_p99:.1f}_with_shed_as_inf")
    out("serve_overload/CLAIM preempt-beats-shed-only-ttft,PASS,exact")
    out("serve_overload/CLAIM batch-flood-still-completes,PASS,exact")
    out("serve_overload/CLAIM zero-stranded-requests,PASS,exact")
    _write_results("serve_overload", results, out)
    return results


def bench_serve_kv_quant(out) -> dict:
    """A/B: bf16 KV block pool vs int8 (per-(block, slot, kv-head) scales)
    at the SAME fixed token budget, seeds, and prompts — decode is
    bandwidth-bound, so the quantized pool should cut decode TPOT by cutting
    the bytes each decode token streams from the pool.

    Always asserts the machine-independent half of the claim: measured
    ``kv_bytes_per_token`` drops >= 1.8x (int8+f32-scales vs bf16 is
    2D/(D+4) = 1.88x at head_dim 64), both streams complete error-free, and
    ``host_syncs == ticks`` per arm.  Outside smoke mode the wall-clock half
    is asserted too: int8 decode TPOT p50 must beat bf16 (on a CPU host this
    measures the XLA-fallback dequant, so the assert rides the non-smoke
    path exactly like serve_mixed_tick's).  Records per-arm
    ``kv_bytes_per_token`` + shape fields so ``roofline.kv_bytes_table``
    can report achieved vs theoretical bandwidth."""
    from repro.serving.engine import ServeEngine
    from repro.models import init_params
    from repro.models.config import ModelConfig
    from repro.serving.scheduler import Request

    smoke = _smoke()
    # head_dim 64 so the int8 byte ratio (2D/(D+4)) clears the 1.8x bar;
    # long-ish contexts so decode actually streams multiple blocks per token
    cfg = ModelConfig(name="bench-kvq", family="dense", n_layers=2,
                      d_model=128, n_heads=2, n_kv_heads=2, head_dim=64,
                      d_ff=128 if smoke else 256, vocab_size=256,
                      dtype="float32", q_chunk=32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    n_slots = 4
    S = 48 if smoke else 160
    decode_new = 12 if smoke else 48
    max_len = 96 if smoke else 256
    budget = 48
    arms = {"baseline": "bfloat16", "int8": "int8"}
    results: dict = {}

    for label, kv_dtype in arms.items():
        rng = np.random.default_rng(23)      # same stream ⇒ same prompts
        eng = ServeEngine(cfg, params, n_slots=n_slots, max_len=max_len,
                          paged=True, block_size=16, token_budget=budget,
                          kv_dtype=kv_dtype)
        done = []
        eng.on_complete = done.append
        t0 = time.monotonic()
        eng.submit(Request(request_id="warm", session_key="warm",
                           prompt=rng.integers(0, cfg.vocab_size, (8,))
                           .astype(np.int32), max_new_tokens=2))
        eng.run_until_drained()
        compile_s = time.monotonic() - t0
        mark = len(eng.stats.tpot_s)
        for i in range(n_slots):
            eng.submit(Request(
                request_id=f"r{i}", session_key=f"s{i}",
                prompt=rng.integers(0, cfg.vocab_size, (S,))
                .astype(np.int32), max_new_tokens=decode_new))
        t0 = time.monotonic()
        eng.run_until_drained()
        wall_s = time.monotonic() - t0
        tpot = eng.stats.tpot_s[mark:]
        assert eng.stats.host_syncs == eng.stats.ticks
        assert all(r.error is None for r in done)
        row = {
            "kv_dtype": kv_dtype,
            "kv_bytes_per_token": eng.cm.kv_bytes_per_token(),
            "n_layers": cfg.n_layers, "n_kv_heads": cfg.n_kv_heads,
            "head_dim": cfg.head_dim,
            "ctx_tokens": S + decode_new,
            "compile_s": compile_s,
            "tpot_p50_s": _pct(tpot, 0.50),
            "tpot_p50_us": _pct(tpot, 0.50) * 1e6,
            "tpot_p99_us": _pct(tpot, 0.99) * 1e6,
            "ticks": eng.stats.ticks,
            "wall_s": wall_s,
        }
        results[label] = row
        out(f"serve_kv_quant/{label},{row['tpot_p50_us']:.1f},"
            f"kv_bytes_per_token={row['kv_bytes_per_token']:.0f} "
            f"tpot_p99_us={row['tpot_p99_us']:.1f} ticks={row['ticks']}")

    byte_ratio = (results["baseline"]["kv_bytes_per_token"]
                  / results["int8"]["kv_bytes_per_token"])
    tpot_ratio = (results["baseline"]["tpot_p50_us"]
                  / max(1e-9, results["int8"]["tpot_p50_us"]))
    results["total"] = {"kv_byte_ratio": byte_ratio,
                        "tpot_ratio_p50": tpot_ratio}
    out(f"serve_kv_quant/byte_ratio,{byte_ratio:.2f},"
        f"tpot_ratio_p50={tpot_ratio:.2f}")
    assert byte_ratio >= 1.8, \
        f"int8 pool must cut KV bytes/token >= 1.8x vs bf16 (got " \
        f"{byte_ratio:.2f}x)"
    out("serve_kv_quant/CLAIM int8-halves-kv-bytes-per-token,PASS,exact")
    if not smoke:
        assert results["int8"]["tpot_p50_us"] \
            < results["baseline"]["tpot_p50_us"], \
            "int8 KV pool failed to beat bf16 decode TPOT p50"
        out("serve_kv_quant/CLAIM int8-beats-bf16-tpot,PASS,exact")
    _write_results("serve_kv_quant", results, out)
    return results


# ----------------------------------------------------------------------
# Replica scaling on mesh slices
# ----------------------------------------------------------------------
def bench_serve_replica_scaling(out) -> dict:
    """2 replicas on 2 DISJOINT mesh slices vs 1 replica on 1 slice, same
    workload: per-driver-pass token throughput must scale near-linearly
    (each pass ticks every busy engine once; with the work split across two
    slices each engine drains in about half the passes).  Wall-clock 2x
    needs the data-parallel tick drivers tracked in ROADMAP item 1 — the
    single-threaded round-robin driver serializes the two slices' ticks, so
    this benchmark asserts the per-pass ratio plus REAL sharded placement:
    disjoint 2-device slices, zero donate misses (sharded publishes stay
    zero-copy), and host_syncs == ticks per engine.

    Needs 4 local devices and runs on the ones it finds: on a CPU host,
    force them before Python starts (``make bench-smoke`` sets
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``)."""
    n_dev = len(jax.devices())
    if n_dev < 4:
        raise RuntimeError(
            f"serve_replica_scaling needs 4 devices (2 slices x 2) but "
            f"found {n_dev} {jax.devices()[0].platform} device(s); on a "
            f"CPU host set XLA_FLAGS=--xla_force_host_platform_device_"
            f"count=8 before starting Python")
    from repro.models import init_params
    from repro.models.config import ModelConfig
    from repro.serving.cluster import ServeCluster
    from repro.serving.engine import EngineStats

    cfg = ModelConfig(name="bench", family="dense", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
                      dtype="float32", q_chunk=16)
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    n_reqs, max_new = (8, 6) if _smoke() else (24, 16)
    prompts = [rng.integers(0, cfg.vocab_size, (8,)).astype(np.int32)
               for _ in range(n_reqs)]

    results: dict = {"devices": len(jax.devices())}
    for arm, n_replicas in (("baseline", 1), ("sharded", 2)):
        with ServeCluster(cfg, params, n_replicas=n_replicas, n_slots=4,
                          max_len=128, devices_per_replica=2) as c:
            # sliced replicas compile their OWN programs (per-slice
            # out_shardings): warm every replica before timing
            for w in range(n_replicas):
                c.submit(f"warmup-{w}", f"w{w}", prompts[0],
                         max_new_tokens=2)
            c.run_until_drained()
            for e in c.engines:
                e.stats = EngineStats()
            t0 = time.monotonic()
            for i, p in enumerate(prompts):
                c.submit(f"sess-{i}", f"r{i}", p, max_new_tokens=max_new)
            c.run_until_drained()
            wall_s = time.monotonic() - t0
            served = sum(c.result(f"r{i}") is not None
                         for i in range(n_reqs))
            st = c.stats()
            pool_dev_sets = [
                sorted(d.id for d in
                       jax.tree.leaves(e.cm.pools)[0].sharding.device_set)
                for e in c.engines]
            results[arm] = {
                "n_replicas": n_replicas,
                "requests": n_reqs,
                "served": served,
                "tokens_out": st["tokens_out"],
                "driver_passes": max(e.stats.ticks for e in c.engines),
                "tokens_per_pass": st["tokens_out"]
                / max(1, max(e.stats.ticks for e in c.engines)),
                "wall_s": wall_s,
                "ttft_p50_us": st["ttft_p50_s"] * 1e6,
                "ttft_p99_us": st["ttft_p99_s"] * 1e6,
                "host_syncs_eq_ticks": all(
                    e.stats.host_syncs == e.stats.ticks for e in c.engines),
                "donate_misses": c.kv_store.donate_misses,
                "pool_devices": pool_dev_sets,
            }

    base, shard = results["baseline"], results["sharded"]
    for arm in (base, shard):
        assert arm["served"] == arm["requests"], f"stranded requests: {arm}"
        assert arm["host_syncs_eq_ticks"], \
            "a sliced engine broke host_syncs == ticks"
        assert arm["donate_misses"] == 0, \
            "sharded pool publish fell off the zero-copy donate path"
        for devs in arm["pool_devices"]:
            assert len(devs) == 2, f"pool leaf not sharded over 2 devices: {devs}"
    assert not set(shard["pool_devices"][0]) & set(shard["pool_devices"][1]), \
        "replica slices share a device"
    ratio = shard["tokens_per_pass"] / max(1e-9, base["tokens_per_pass"])
    results["total"] = {"tokens_per_pass_ratio": ratio,
                        "ttft_p99_us": shard["ttft_p99_us"]}
    out(f"serve_replica_scaling/baseline,{base['ttft_p99_us']:.1f},"
        f"tokens_per_pass={base['tokens_per_pass']:.2f}")
    out(f"serve_replica_scaling/sharded,{shard['ttft_p99_us']:.1f},"
        f"tokens_per_pass={shard['tokens_per_pass']:.2f} "
        f"ratio={ratio:.2f}")
    out("serve_replica_scaling/CLAIM disjoint-slices-sharded-pool,PASS,exact")
    out("serve_replica_scaling/CLAIM sharded-publish-zero-copy,PASS,exact")
    if not _smoke():
        assert ratio >= 1.8, \
            f"2 slices must deliver ~2x per-pass token throughput " \
            f"(got {ratio:.2f}x)"
        out("serve_replica_scaling/CLAIM two-slices-near-linear,PASS,exact")
    _write_results("serve_replica_scaling", results, out)
    return results
