"""Chip smoke run: the serving main path at full model width on a TPU.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # four chips, the mesh-sliced path only

One chip: h2o-danube-1.8b at its published widths in bf16, with random
weights from ``--seed``, served the way a user serves it —
``ServeNode.deploy`` → ``ModelDeployment.submit`` (store → dispatcher →
upcall) → ``ServeEngine`` → ``PagedCacheManager`` → the unified tick → the
compiled Pallas ragged kernel.  Four sessions share a 512-token system
prompt per session and add user turns of 32-1024 tokens, so prefix reuse
and chunked prefill both run.  A second deployment on the same node serves
the same weights from an int8 KV pool (the in-kernel dequant path).  The
bf16 answers are checked against a teacher-forced ``models.forward``
reference on the XLA path, with no cache.

Four chips (``--four-chips``): h2o-danube-3-4b on one 4-device mesh slice
(``devices_per_replica=4``; the ragged kernel runs per shard), against the
same weights on one chip, served first and undeployed before the slice.

The times printed are those of a smoke run, not measurements.  The script
exits non-zero when JAX finds no TPU, and on any failed check; its last
line of output is then not the JSON result line.
"""
from __future__ import annotations

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no libtpu logs in /tmp

import argparse
import gc
import json
import re
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# Teacher-forced log-prob agreement between the engine (paged pool, ragged
# kernel with f32 softmax) and the XLA reference (dense causal attention,
# bf16 probabilities): both run bf16 weights and activations, whose
# rounding (2^-9 relative) compounds over 24 layers into logit errors of
# order 1e-2 at the O(1) logit scale ``make_params`` sets.  A wrong block,
# head or position moves a log-prob by O(1).
LOGPROB_TOL = 0.15
# The int8 pool adds per-token absmax quantization of every cached K/V
# (half a step is 1/254 of the row's largest magnitude) in every layer.
INT8_TOL = 0.3
# Sharded vs one-chip tick (same kernel, same weights): only the order of
# the sharded matmul reductions differs, in bf16.
SHARDED_TOL = 0.15


def log(msg: str) -> None:
    print(msg, flush=True)


def device_info() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def make_params(cfg, seed: int):
    """Random weights from ``seed``, built on the device.  The output head
    is rescaled to fan-in variance: init's N(0, 1) table gives logits with
    std ~sqrt(d_model) (~50), where every greedy log-prob is ~0 and a
    log-prob comparison sees nothing; at std ~1 it moves with every layer."""
    from repro.models import init_params

    params = jax.jit(init_params, static_argnums=1)(
        jax.random.PRNGKey(seed), cfg)
    head = "embed" if cfg.tie_embeddings else "head"
    table = params[head]["table"]
    params[head] = {"table": table * jnp.asarray(cfg.d_model ** -0.5,
                                                 table.dtype)}
    return params


def session_traffic(seed: int, vocab: int, *, sessions: int, turns: int,
                    system_len: int, suffix: tuple[int, int]):
    """(session, request_id, prompt) per request: every turn of a session
    starts with that session's system prompt, then a fresh user suffix."""
    rng = np.random.default_rng(seed)
    out = []
    for s in range(sessions):
        system = rng.integers(0, vocab, system_len)
        for t in range(turns):
            n = int(rng.integers(suffix[0], suffix[1] + 1))
            prompt = np.concatenate([system, rng.integers(0, vocab, n)])
            out.append((f"sess{s}", f"s{s}t{t}", prompt.astype(np.int32)))
    return out


def check_compiled_kernel(name: str, eng, hlo: str) -> None:
    """The tick runs the compiled Pallas kernel: no interpret mode, no XLA
    densify fallback."""
    assert eng.attn_backend == "pallas", \
        f"{name}: tick attention runs {eng.attn_backend!r}, not the kernel"
    assert "tpu_custom_call" in hlo, f"{name}: no Pallas kernel in the tick"


def serve(node, name: str, cfg, params, traffic, *, max_new: int,
          timeout_s: float, **deploy_kw) -> dict:
    """Deploy, compile the tick, serve ``traffic`` through the store's fast
    path, drain, and check every pass condition of the path."""
    dep = node.deploy(name, cfg, params, n_replicas=1, **deploy_kw)
    done: dict = {}
    dep.on_done.append(lambda req: done.__setitem__(req.request_id, req))
    eng = dep.engines[0]
    t0 = time.perf_counter()
    compiled = eng.lower_tick().compile()
    compile_s = time.perf_counter() - t0
    hlo = compiled.as_text()
    check_compiled_kernel(name, eng, hlo)
    t0 = time.perf_counter()
    for session, rid, prompt in traffic:
        dep.submit(session, rid, prompt, max_new_tokens=max_new)
    node.run_until_drained(timeout_s=timeout_s)
    wall_s = time.perf_counter() - t0
    for _, rid, _ in traffic:
        req = done.get(rid)
        assert req is not None, f"{name}/{rid}: never completed"
        assert req.error is None and dep.error(rid) is None, \
            f"{name}/{rid}: {req.error or dep.error(rid)}"
        assert len(req.tokens) == max_new == len(dep.result(rid)), \
            f"{name}/{rid}: {len(req.tokens)} of {max_new} tokens"
    errors = sum(w.dispatcher.stats()["upcall_errors"] for w in node.workers)
    assert errors == 0, f"{name}: {errors} upcall error(s)"
    st = eng.stats
    assert st.host_syncs == st.ticks, \
        f"{name}: host_syncs {st.host_syncs} != ticks {st.ticks}"
    log(f"{name}: compile_s={compile_s:.1f} ticks={st.ticks} "
        f"tokens_out={st.tokens_out} prefix_hit_tokens={st.prefix_hit_tokens} "
        f"prefill_chunks={st.prefill_chunks} wall_s={wall_s:.1f} "
        f"(smoke run, not a measurement)")
    mem = compiled.memory_analysis()
    log(f"{name}: tick argument_bytes={mem.argument_size_in_bytes} "
        f"temp_bytes={mem.temp_size_in_bytes}")
    return {"dep": dep, "reqs": [done[rid] for _, rid, _ in traffic],
            "hlo": hlo}


def reference_logprobs(params, cfg, reqs, batch: int = 4) -> list:
    """Per request, log p(emitted token j) under a teacher-forced
    ``models.forward`` over prompt + emissions (XLA attention, no cache)."""
    from repro.models import forward

    ref_cfg = cfg.replace(attn_backend="xla")
    seqs = [np.concatenate([np.asarray(r.prompt), r.tokens[:-1]])
            for r in reqs]
    J = max(len(r.tokens) for r in reqs)
    L = -(-max(len(s) for s in seqs) // 128) * 128

    @jax.jit
    def score(params, toks, idx, tgt):
        pos = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), toks.shape)
        logits, _ = forward(params, toks, pos, ref_cfg, mode="score")
        lg = jnp.take_along_axis(logits, idx[..., None], axis=1)
        lp = jax.nn.log_softmax(lg.astype(jnp.float32), axis=-1)
        return jnp.take_along_axis(lp, tgt[..., None], axis=-1)[..., 0]

    out = []
    for b in range(0, len(reqs), batch):
        chunk = reqs[b:b + batch]
        toks = np.zeros((batch, L), np.int32)
        idx = np.zeros((batch, J), np.int32)
        tgt = np.zeros((batch, J), np.int32)
        for i, r in enumerate(chunk):
            s = seqs[b + i]
            toks[i, :len(s)] = s
            n = len(r.tokens)
            idx[i, :n] = len(r.prompt) - 1 + np.arange(n)
            tgt[i, :n] = r.tokens
        lp = np.asarray(score(params, toks, idx, tgt))
        out += [lp[i, :len(r.tokens)] for i, r in enumerate(chunk)]
    return out


def max_logprob_diff(reqs, ref) -> float:
    return max(float(np.max(np.abs(np.asarray(r.scores) - lp)))
               for r, lp in zip(reqs, ref))


def pool_sized_collectives(hlo: str, pool_leaf_elems: int) -> list[str]:
    """Collectives in compiled HLO whose result holds at least one whole
    per-layer pool leaf: what gathering the pool onto a device looks like."""
    bad = []
    for line in hlo.splitlines():
        m = re.search(r"= (.+?) (all-gather|all-reduce|all-to-all|"
                      r"collective-permute|reduce-scatter)(-start)?\(", line)
        if not m:
            continue
        for dims in re.findall(r"\w+\[([\d,]+)\]", m.group(1)):
            if int(np.prod([int(d) for d in dims.split(",")])) \
                    >= pool_leaf_elems:
                bad.append(line.strip()[:160])
    return bad


def release(node, name: str) -> None:
    """Undeploy and drop the deployment's device buffers now."""
    node.undeploy(name)
    gc.collect()


def serve_one_chip(cfg, params, traffic, *, max_new: int, **deploy_kw):
    """The one-chip phase on a given config: a bf16-pool deployment serves
    ``traffic``; an int8-pool deployment of the same weights, on the same
    node, serves each session's first turn; both are checked against the
    reference."""
    from repro.serving.cluster import ServeNode

    with ServeNode() as node:
        bf16 = serve(node, "bf16-pool", cfg, params, traffic,
                     max_new=max_new, **deploy_kw)
        assert bf16["dep"].stats()["prefix_hit_tokens"] > 0, \
            "no prefix was reused across a session's turns"
        first_turns = [t for t in traffic if t[1].endswith("t0")]
        q8 = serve(node, "int8-pool", cfg, params, first_turns,
                   max_new=max_new, kv_dtype="int8", **deploy_kw)
        reqs, q8_reqs = bf16["reqs"], q8["reqs"]
        del bf16, q8
        release(node, "bf16-pool")
        release(node, "int8-pool")
    for name, rs, tol in (("bf16 pool", reqs, LOGPROB_TOL),
                          ("int8 pool", q8_reqs, INT8_TOL)):
        diff = max_logprob_diff(rs, reference_logprobs(params, cfg, rs))
        log(f"{name}: max |logprob - reference| = {diff:.5f} over "
            f"{len(rs)} requests (tolerance {tol})")
        assert diff <= tol, f"{name}: logprobs off by {diff}"


def serve_sliced(cfg, params, traffic, *, max_new: int, devices: int,
                 **deploy_kw):
    """The mesh-slice phase on a given config: one replica on one chip,
    undeployed, then one replica on a ``devices``-device slice, serving the
    same traffic; checked against each other and the reference."""
    from repro.serving.cluster import ServeNode

    with ServeNode() as node:
        single = serve(node, "one-chip", cfg, params, traffic,
                       max_new=max_new, **deploy_kw)
        single_reqs = single["reqs"]
        del single
        release(node, "one-chip")
        sliced = serve(node, "sliced", cfg, params, traffic,
                       max_new=max_new, devices_per_replica=devices,
                       **deploy_kw)
        leaves = jax.tree.leaves(sliced["dep"].engines[0].cm.pools)
        for leaf in leaves:
            assert len(leaf.sharding.device_set) == devices, leaf.sharding
        misses = node.kv_store().donate_misses
        assert misses == 0, f"{misses} pool publish(es) copied the pool"
        bad = pool_sized_collectives(sliced["hlo"],
                                     int(np.prod(leaves[0].shape[1:])))
        assert not bad, f"pool-sized collectives in the tick: {bad}"
        sliced_reqs = sliced["reqs"]
        del sliced, leaves
        release(node, "sliced")
    # sliced vs one chip, up to and including the first token where the
    # greedy streams part (a near-tie: both log-probs sit at the tie)
    pair = 0.0
    for a, b in zip(single_reqs, sliced_reqs):
        same = [x == y for x, y in zip(a.tokens, b.tokens)] + [False]
        k = same.index(False) + 1
        pair = max(pair, float(np.max(np.abs(
            np.asarray(a.scores[:k]) - np.asarray(b.scores[:k])))))
    log(f"sliced vs one chip: max |logprob diff| = {pair:.5f} "
        f"(tolerance {SHARDED_TOL})")
    assert pair <= SHARDED_TOL, f"sliced logprobs off by {pair}"
    for name, rs in (("one chip", single_reqs), ("sliced", sliced_reqs)):
        diff = max_logprob_diff(rs, reference_logprobs(params, cfg, rs))
        log(f"{name}: max |logprob - reference| = {diff:.5f} "
            f"(tolerance {LOGPROB_TOL})")
        assert diff <= LOGPROB_TOL, f"{name}: logprobs off by {diff}"


def build(arch: str, seed: int):
    from repro.configs.registry import get_config

    cfg = get_config(arch)
    t0 = time.perf_counter()
    params = make_params(cfg, seed)
    jax.block_until_ready(params)
    log(f"{arch}: {cfg.param_count()} params in {cfg.dtype}, built in "
        f"{time.perf_counter() - t0:.1f}s")
    return cfg, params


def one_chip(seed: int) -> None:
    cfg, params = build("h2o-danube-1.8b", seed)
    traffic = session_traffic(seed, cfg.vocab_size, sessions=4, turns=4,
                              system_len=512, suffix=(32, 1024))
    serve_one_chip(cfg, params, traffic, max_new=32, n_slots=8,
                   max_len=4096, block_size=16, token_budget=256,
                   timeout_s=900.0)


def four_chips(seed: int) -> None:
    n = len(jax.devices())
    assert n >= 4, f"--four-chips needs 4 devices, found {n}"
    cfg, params = build("h2o-danube-3-4b", seed)
    traffic = session_traffic(seed, cfg.vocab_size, sessions=2, turns=2,
                              system_len=256, suffix=(32, 256))
    serve_sliced(cfg, params, traffic, max_new=16, devices=4, n_slots=4,
                 max_len=1024, block_size=16, token_budget=256,
                 timeout_s=600.0)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-device mesh-slice phase")
    args = ap.parse_args()
    info = device_info()
    if info["platform"] != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found platform "
                 f"{info['platform']!r} ({info['kind']})")
    from repro.launch.compile_cache import enable_compile_cache

    log(f"device_kind: {info['kind']} x{info['count']}; compile cache "
        f"{enable_compile_cache()}")
    (four_chips if args.four_chips else one_chip)(args.seed)
    peak = jax.devices()[0].memory_stats()["peak_bytes_in_use"]
    log(f"device 0 peak_bytes_in_use: {peak}")
    print(json.dumps({"ok": True, "device": info}), flush=True)


if __name__ == "__main__":
    main()
