"""The serving engine: Cascade hosting applied to LM inference.

One engine replica = one DFG vertex (a lambda bound to /serve/<name>) whose
"computation" is prefill+decode over a model whose weights live in the
replica's device store — data/compute collocation: requests (small objects)
move to the weights (the largest dependency), never the reverse (§2, §3.5).

Unified token-budget tick (paged mode — the default for pure-attention token
models, see ``models.supports_paged``)
--------------------------------------------------------------------------
Every tick is ONE fixed-shape jitted mixed step.  The scheduler admits work
against a per-tick TOKEN budget: each active decode row costs 1 token, and
waiting prefills are split into chunks that fill the remainder — a long
prompt spreads over several ticks instead of stalling every decoding session
behind it (the head-of-line effect the paper's fast path exists to kill; the
inter-token stall is bounded by the chunk budget).  The admitted tokens are
packed into a single ragged batch — per-token absolute positions and request
row ids — and a ragged paged-attention step (kernels/decode_attention)
computes prefill chunks and decode rows in the SAME dispatch against the
shared block pool: all packed K/V is written before any packed token reads,
so intra-chunk causality, decode, and intra-batch prefix sharing (a
same-tick sibling attending to a chunk's just-written prefix blocks) are all
one causal mask.

Fast-path discipline of the unified tick:

- **Fixed shapes, one compile** — the packed batch is always exactly
  ``token_budget`` tokens and the block-table operand is always
  (n_slots, max_blocks), so the step compiles ONCE for the engine's
  lifetime: no per-prompt-length (or per-suffix-length) recompiles, no
  cold-turn TTFT tail from XLA.
- **Fused boundary sampling + scoring** — the head + sampler run inside the
  step on one gathered boundary token per slot (its decode token, or the
  final token of the chunk that completed its prompt), so the host never
  sees logits: only an (n_slots,) token vector plus an (n_slots, 2) score
  vector — log p(token) and the next-token distribution's entropy, computed
  from the same in-dispatch log-softmax.  Those per-token scores are what
  cascade gates (serving/cluster.CascadeRoute) read to decide light→heavy
  escalation; the engine already has them on device, so surfacing them
  costs no extra dispatch and no extra logits traffic.
- **One device→host sync per tick** — tokens and scores are pulled together
  in one blocking ``jax.device_get``; ``stats.host_syncs == stats.ticks``
  is THE invariant (``_to_host`` counts every sync point; an idle tick —
  nothing live, nothing admissible — dispatches nothing and does not count
  as a tick).

Speculative decoding (``spec_k > 0``, paged engines only — gated by
``models.supports_speculative`` exactly as paging is by ``supports_paged``):
a ``DraftSource`` (serving/draft) proposes up to ``spec_k`` draft tokens per
decode row — self-drafted from the request's own prompt+generated history,
or carried on the request by a cascade (the light deployment's generation).
The row packs ``[t_last, d_1, .., d_m]`` as m+1 consecutive tokens in the
SAME ragged dispatch (the kernel already treats a multi-token row like a
prefill chunk: K/V written first, causal mask per token), the head gathers
all m+1 boundary logits, and the in-dispatch acceptance rule
(``models.sampling.speculative_verify`` — Leviathan-style rejection
sampling) keeps the longest target-confirmed prefix plus one
correction/bonus token.  The host still sees ONE sync per tick, now
amortized over up to m+1 emitted tokens; KV written for rejected drafts is
rolled back by truncating the row's block table
(``kvcache.rollback_writes``).  Budget arithmetic: draft lanes are granted
LAST — after every live row's mandatory lane and all prefill chunk work has
packed (``_plan_drafts``) — so a k-token row can never oversubscribe the
fixed packed shape, never starves a sibling decode row, and never delays a
waiting prefill: speculation monetizes lanes that would have dispatched as
pads.  Greedy speculation emits the bit-identical stream of the
non-speculative engine; sampled speculation emits exactly the target
distribution (rejection sampling is lossless).

Prefix reuse: admission matches each prompt against the per-replica trie of
cached token blocks and prefills ONLY the suffix past the last matched block
(``stats.prefix_hit_tokens``).  Chunk-granularity trie commit
(kvcache.commit_prefill_progress) extends that to SAME-TICK sharing: two
same-prefix requests admitted in one tick share blocks instead of both
prefilling the prefix.

Dense mode (SSM/hybrid/embeds configs, ``supports_paged == False``) keeps
the phase-separated discipline: batched equal-length prefill admission (one
jitted prefill per contiguous same-shape run), masked fused decode+sample,
and ``host_syncs == decode_ticks + prefill_batches``.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import (decode_step, paged_mixed_step, prefill,
                          sample_with_scores, speculative_verify,
                          supports_paged, supports_speculative)
from repro.models.attention import paged_attn_backend
from repro.models.config import ModelConfig

from .draft import DraftSource, default_draft_source
from .faults import ReplicaCrashed
from .kvcache import CacheManager, PagedCacheManager, SpilledKV
from .scheduler import Request, Scheduler, virtual_deadline


@dataclass
class EngineStats:
    ticks: int = 0                 # dispatched steps (paged) / tick() calls (dense)
    tokens_out: int = 0
    prefills: int = 0
    prefill_batches: int = 0       # dense: jitted prefill dispatches (paged: 0)
    prefill_chunks: int = 0        # paged: prompt chunks packed into mixed steps
    decode_ticks: int = 0          # ticks that carried >= 1 decode row
    host_syncs: int = 0            # device→host transfers
    prompt_tokens: int = 0         # total prompt tokens seen
    prefill_tokens: int = 0        # tokens actually prefilled
    prefix_hit_tokens: int = 0     # tokens reused from cache
    prefix_hits: int = 0           # requests with a hit
    blocks_in_use: int = 0         # gauge, sampled per tick
    # speculative decoding (paged engines with spec_k > 0):
    spec_drafted: int = 0          # draft tokens packed for verification
    spec_accepted: int = 0         # drafts the target confirmed (kept)
    spec_rolled_back: int = 0      # rejected drafts whose KV was rolled back
    # fault tolerance (serving/faults, deployment failover):
    deadline_exceeded: int = 0     # requests expired at this replica
    spill_syncs: int = 0           # device→host KV spills (counted in
    #                                host_syncs too: a spilled — dead —
    #                                replica satisfies host_syncs == ticks
    #                                + spill_syncs; survivors keep the
    #                                strict host_syncs == ticks)
    spilled_sessions: int = 0      # live sessions spilled off this replica
    adopted_sessions: int = 0      # migrated sessions restored INTO this one
    # overload preemption (issue-queue scheduler, preempt=True):
    preemptions: int = 0           # in-flight victims evicted for a waiter
    spilled_blocks: int = 0        # KV blocks pulled host-side by spills
    resumes: int = 0               # preempted requests restored via adopt()
    #                                (replay-fallback resumes re-issue as
    #                                ordinary admissions and are NOT counted)
    ttft_s: list = field(default_factory=list)     # time to first token
    tpot_s: list = field(default_factory=list)     # time per output token
    # per-SLO-class queue wait (issued_s - arrived_s), recorded once at a
    # request's FIRST issue (a preempted request keeps its original wait)
    queue_wait_s: dict = field(default_factory=dict)   # slo -> [seconds]

    def spec_acceptance_rate(self) -> float:
        """Fraction of drafted tokens the target model confirmed."""
        return (self.spec_accepted / self.spec_drafted
                if self.spec_drafted else float("nan"))


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params, *, n_slots: int = 8,
                 max_len: int = 512, temperature: float = 0.0,
                 scheduler: Scheduler | None = None, replica_id: int = 0,
                 on_complete: Callable[[Request], None] | None = None,
                 seed_offset: int | None = None, paged: bool | None = None,
                 block_size: int = 16, num_blocks: int | None = None,
                 prefix_cache: bool = True, devstore=None,
                 kv_key: str | None = None,
                 kv_dtype: str | None = None,
                 token_budget: int | None = None,
                 spec_k: int = 0,
                 draft_source: DraftSource | None = None,
                 spill_pool=None,
                 preempt: bool = False,
                 mesh=None) -> None:
        self.cfg = cfg
        self.paged = supports_paged(cfg) if paged is None else paged
        if self.paged and not supports_paged(cfg):
            raise ValueError(f"config {cfg.name} cannot use the paged cache")
        # Mesh slice (tensor-parallel replica): params install sharded over
        # the slice per the logical-axis rules, and the unified tick compiles
        # against the slice's mesh.  Paged-only: the dense slot cache has no
        # leaf-axis story, and every config we serve sharded is paged anyway.
        self.mesh = mesh
        if mesh is not None:
            if not self.paged:
                raise ValueError(
                    "mesh slices shard the paged block pool; the dense cache "
                    "path only runs single-device (pass paged=True or a "
                    "config with supports_paged)")
            from repro.launch.sharding import param_shardings
            params = jax.device_put(params, param_shardings(cfg, mesh))
        self.params = params
        self.spec_k = int(spec_k)
        if self.spec_k < 0:
            raise ValueError(f"spec_k={spec_k} must be >= 0")
        if self.spec_k and (not self.paged or not supports_speculative(cfg)):
            raise ValueError(
                f"config {cfg.name} cannot decode speculatively: multi-token "
                f"verify rows and KV rollback need the paged path "
                f"(supports_speculative)")
        self.draft_source = (draft_source if draft_source is not None
                             else (default_draft_source() if self.spec_k
                                   else None))
        if self.paged:
            self.cm: Any = PagedCacheManager(
                cfg, n_slots, max_len, block_size=block_size,
                num_blocks=num_blocks, prefix_cache=prefix_cache,
                devstore=devstore, kv_key=kv_key, kv_dtype=kv_dtype,
                mesh=mesh)
            self.token_budget = (token_budget if token_budget is not None
                                 else max(32, 2 * n_slots))
            if self.token_budget < n_slots:
                raise ValueError(
                    f"token_budget={self.token_budget} < n_slots={n_slots}: "
                    f"every live decode row costs one token per tick, so a "
                    f"smaller budget would starve decodes")
        else:
            from repro.kernels.decode_attention.quant import is_quantized
            if is_quantized(kv_dtype):
                raise ValueError(
                    f"kv_dtype={kv_dtype!r} quantizes paged KV blocks; the "
                    f"dense slot cache has no block pool to quantize")
            self.cm = CacheManager(cfg, n_slots, max_len)
            self.token_budget = None
        # Preemption (opt-in, paged only): under pressure the tick may evict
        # one in-flight victim with a strictly later virtual deadline than
        # the best waiting request, spilling its KV through the one sync
        # site into ``spill_pool`` (core.store.SpillPool; parked entries
        # restore via adopt(), with prompt replay when the pool evicted
        # them).  Off by default: a non-preempting engine's tick stream and
        # sync accounting are bit-identical to before this feature existed.
        self.preempt = bool(preempt)
        self.spill_pool = spill_pool
        if self.preempt and not self.paged:
            raise ValueError("preemption spills paged KV blocks; the dense "
                             "path has no per-request blocks to spill")
        self.scheduler = scheduler or Scheduler(n_replicas=1)
        self.replica_id = replica_id
        self.temperature = temperature
        self.on_complete = on_complete
        self.stats = EngineStats()
        self.live: dict[int, Request] = {}         # slot → decoding request
        self.prefilling: dict[int, Request] = {}   # slot → mid-prompt request
        # fault-tolerance state (serving/faults + deployment failover):
        # ``faults`` is an injector seam bound by ModelDeployment
        # .install_faults; ``crashed`` makes tick/submit raise
        # ReplicaCrashed (set by an injected crash or the deployment's
        # mark_down, BEFORE evacuation, so racing submits bounce to a
        # sibling instead of landing in a drained queue).
        self.faults = None
        self.crashed = False
        self.kv_recoverable = True
        if self.paged:
            # host-side last emitted token per slot: the mixed tick composes
            # its packed batch on host, so no device token vector is needed
            self._last_host = np.zeros((n_slots,), np.int64)
        else:
            self._last_tokens = jnp.zeros((n_slots,), jnp.int32)
        # Sampling seed stream: one fresh seed per jitted dispatch, offset by
        # replica so same-tick dispatches / sibling replicas never share a
        # PRNG key.
        self._seed_base = (seed_offset if seed_offset is not None
                           else replica_id) * 1_000_003
        self._dispatches = 0

        temp = temperature

        def _sample(logits, seed):
            """Sample + score in-dispatch (models.sampling): (tokens (B,),
            scores (B, 2)) with scores = [log p(token), entropy], both from
            the same log-softmax the sampler needs anyway — cascade gates
            get their confidence signal without the host seeing logits."""
            return sample_with_scores(logits, seed, temp)

        # the backend the paged tick's attention runs on (models.attention
        # .paged_attn_backend): the compiled kernel on a TPU unless the
        # config pins another
        self.attn_backend = paged_attn_backend(cfg) if self.paged else None

        # Paged mode donates the pool operand: the step scatters into every
        # layer's pool leaf, and without donation XLA must copy the whole
        # global block pool ((num_blocks, block_size, K, D) per layer) on
        # every dispatch — at realistic pool sizes that copy negates the
        # paging win.  Each dispatch replaces ``cm.pools`` with the returned
        # tree and ``publish()`` re-installs the fresh leaves.  Discipline:
        # between a dispatch and its publish() the devstore's /kv entry
        # aliases the donated (deleted) buffers, so KV reads through the
        # store must come from the tick thread (the engine's one-driver
        # model), never concurrently from another thread.
        #
        # The sampler side is speculative_verify over (R, spec_k+1) gathered
        # boundary logits: with spec_k == 0 every row has draft_len 0 and
        # the verify degenerates to plain sampling at position 0, so ONE
        # code path (and one compiled program) serves both modes.
        if self.paged:
            def _mixed(p, pools, bt, toks, pos, rows, sample_idx,
                       draft_toks, draft_len, seed):
                # a sliced tick traces under its slice's mesh, so the ragged
                # kernel runs per shard (models.attention, shard_map)
                with (jax.sharding.use_abstract_mesh(mesh.abstract_mesh)
                      if mesh is not None else contextlib.nullcontext()):
                    logits, pools = paged_mixed_step(p, pools, bt, toks, pos,
                                                     rows, sample_idx, cfg)
                tok, n_acc, score = speculative_verify(logits, draft_toks,
                                                       draft_len, seed, temp)
                return tok, n_acc, score, pools

            if mesh is not None:
                # Pin output shardings: XLA's propagation is free to pick a
                # different layout for the donated pool output, which would
                # break the devstore's exact-match donate gate and turn every
                # publish into a cross-device copy.  Tokens/scores replicate
                # (tiny vectors, pulled host-side each tick anyway).
                from jax.sharding import NamedSharding, PartitionSpec
                rep = NamedSharding(mesh, PartitionSpec())
                self._mixed = jax.jit(
                    _mixed, donate_argnums=(1,),
                    out_shardings=(rep, rep, rep, self.cm.pool_shardings))
            else:
                self._mixed = jax.jit(_mixed, donate_argnums=(1,))
        else:
            def _prefill_step(p, toks, pos, seed):
                logits, caches = prefill(p, toks, pos, cfg, max_len=max_len)
                tok, score = _sample(logits, seed)
                return tok, score, caches

            def _decode_tick(p, caches, toks, pos, active, seed):
                logits, new_caches = decode_step(p, caches, toks, pos, cfg)
                sampled, score = _sample(logits, seed)
                # masked decode: inactive slots keep their last token so stale
                # rows never feed garbage back into the next step
                return jnp.where(active, sampled, toks), score, new_caches

            self._prefill = jax.jit(_prefill_step)
            self._step = jax.jit(_decode_tick)

    # ------------------------------------------------------------- client
    def submit(self, req: Request) -> None:
        """Enqueue a request, or reject it up front through the completion
        path (``req.error`` set, ``on_complete`` fired, nothing enqueued)
        when it could never be served: an oversized request must not blow up
        mid-admission, and one whose worst-case block demand exceeds what the
        pool can EVER provide must not park at the head of the queue
        forever."""
        if self.crashed:
            raise ReplicaCrashed(
                f"replica {self.replica_id} is marked down")
        if self.faults is not None:
            self.faults.on_submit()          # may raise InjectedFault
        if req.expired():
            self._deadline_error(req, "admission")
            return
        req.prompt = self._norm_prompt(req.prompt)   # normalize ONCE: every
        err = self._validate(req)                    # later pass is a no-op
        if err is not None:
            self._reject(req, err)
            return
        self.scheduler.submit(req)

    def _validate(self, req: Request) -> str | None:
        S = len(self._norm_prompt(req.prompt))
        if S > self.cm.max_len:
            return f"prompt of {S} tokens exceeds max_len={self.cm.max_len}"
        if self.paged:
            # the paged pool has no ring fallback: a decode that reaches
            # max_len has no block to write and would kill the whole tick.
            # A replayed request's prompt carries replay_offset already-
            # generated tokens folded in, which max_new_tokens still counts
            # — subtract them so accounting matches the uninterrupted run.
            S_eff = S - req.replay_offset
            if self.cm.written_max(S_eff, req.max_new_tokens) > self.cm.max_len:
                return (f"prompt of {S} tokens + {req.max_new_tokens} new "
                        f"tokens would write past max_len={self.cm.max_len}")
            # with the pool drained and the prefix cache fully evicted, at
            # most num_blocks-1 blocks exist (block 0 is the null block)
            cap = self.cm.num_blocks - 1
            need = self._block_cost(req)
            if need > cap:
                return (f"request needs up to {need} KV blocks but the pool "
                        f"can ever provide {cap} (raise num_blocks or lower "
                        f"max_new_tokens)")
        return None

    def _reject(self, req: Request, err: str) -> None:
        req.error = err
        self._complete(req)

    def _deadline_error(self, req: Request, stage: str) -> None:
        """Expire a request through the completion path with a STRUCTURED
        reason (stage = where the budget ran out); partial tokens are kept —
        a deadline is a latency bound, not a correctness failure."""
        now = time.monotonic()
        self.stats.deadline_exceeded += 1
        req.error = {"error": "deadline_exceeded", "stage": stage,
                     "deadline_s": req.deadline_s,
                     "elapsed_s": now - req.arrived_s,
                     "request_id": req.request_id}
        self._complete(req)

    def _sweep_deadlines(self) -> None:
        """Per-tick deadline enforcement over every stage a request can be
        parked in: queued (never admitted), mid-prefill, and decoding.
        Runs at tick entry so an expired request never consumes another
        dispatch; slots are released with exact accounting (a decoding
        slot's written blocks are finished/cached — its KV is valid — and
        a prefilling slot's refs are dropped, trie residency untouched)."""
        now = time.monotonic()
        for req in self.scheduler.pop_expired(self.replica_id, now):
            self._deadline_error(req, "queued")
        for slot, req in list(self.prefilling.items()):
            if req.expired(now):
                self.prefilling.pop(slot)
                self.cm.release(slot)
                self._deadline_error(req, "prefill")
        for slot, req in list(self.live.items()):
            if req.expired(now):
                self.live.pop(slot)
                self._release_slot(slot, req)
                self._deadline_error(req, "decode")

    # ------------------------------------------------------------- engine
    def _next_seed(self) -> jnp.ndarray:
        self._dispatches += 1
        return jnp.int32(self._seed_base + self._dispatches)

    # lint: sync-site(THE one per-tick device->host pull)
    def _to_host(self, arr):
        """THE device→host sync point; everything host-side reads through
        here so tests/benchmarks can assert the one-sync-per-tick rule.
        Accepts any pytree — a (tokens, scores) tuple, a single array, or a
        spilled KV block tree — pulled in ONE blocking ``jax.device_get``:
        still a single sync per call."""
        self.stats.host_syncs += 1
        return jax.tree.map(np.asarray, jax.device_get(arr))

    @staticmethod
    def _norm_prompt(prompt) -> np.ndarray:
        """(S,) tokens or (S,d) embeds; squeeze a legacy leading batch dim."""
        p = np.asarray(prompt)
        if p.ndim >= 2 and p.shape[0] == 1:
            p = p[0]
        if np.issubdtype(p.dtype, np.integer) and p.dtype != np.int32:
            p = p.astype(np.int32)
        return p

    def _block_cost(self, req: Request) -> int:
        """Worst-case block footprint of a request (reuse only shrinks it).
        Replayed requests subtract ``replay_offset``: the folded tokens
        would have been written as decode feedbacks anyway, so the replayed
        footprint equals the uninterrupted one — exact accounting across a
        failover."""
        S = len(self._norm_prompt(req.prompt)) - req.replay_offset
        return self.cm.block_cost(S, req.max_new_tokens)

    def idle(self) -> bool:
        return (self.scheduler.pending(self.replica_id) == 0
                and not self.live and not self.prefilling)

    def backlog(self) -> int:
        """Requests this replica currently holds: queued + mid-prefill +
        decoding.  The admission-control signal bounded per-replica queues
        (serving/cluster.ModelDeployment) compare against their watermark."""
        return (self.scheduler.pending(self.replica_id)
                + len(self.prefilling) + len(self.live))

    # ==================================================== dense admission
    def _admit_dense(self) -> None:
        # Sweep expired entries IMMEDIATELY before batch admission (the
        # tick-entry sweep is not enough when admission is driven outside
        # tick(), e.g. run loops calling _admit_dense directly): a dead head
        # must never consume a free slot or a prefill-budget lane, and must
        # error out as deadline_exceeded rather than be served late.
        for req in self.scheduler.pop_expired(self.replica_id):
            self._deadline_error(req, "queued")
        free = self.cm.n_slots - self.cm.n_active
        reqs = self.scheduler.admit(self.replica_id, free)
        if not reqs:
            return
        for req in reqs:
            self._record_issue(req)
        # Batched multi-request prefill: batch CONTIGUOUS same-shape runs
        # (equal-length bucketing — no padding, so ring caches and SSM state
        # stay exact), one jitted prefill and ONE host pull per run.
        # Contiguity (not a shape→list dict) preserves admission order, so
        # a FIFO session's turns can never be prefilled out of order.
        groups: list[tuple[tuple, list[tuple[Request, np.ndarray]]]] = []
        for req in reqs:
            p = self._norm_prompt(req.prompt)
            if groups and groups[-1][0] == p.shape:
                groups[-1][1].append((req, p))
            else:
                groups.append((p.shape, [(req, p)]))
        for shape, group in groups:
            prompts = jnp.asarray(np.stack([p for _, p in group]))
            S = shape[0]
            pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32),
                                   (len(group), S))
            toks, scores, group_caches = self._prefill(self.params, prompts,
                                                       pos, self._next_seed())
            host_toks, host_scores = self._to_host((toks, scores))
            self.stats.prefill_batches += 1           # one sync per group
            now = time.monotonic()
            for row, (req, p) in enumerate(group):
                slot = self.cm.acquire(req.request_id)
                assert slot is not None
                self.cm.insert_prefill(slot, group_caches, S, row)
                self.stats.prompt_tokens += S
                self.stats.prefill_tokens += S
                self._finish_admission(req, slot, int(host_toks[row]), now,
                                       host_scores[row])

    def _finish_admission(self, req: Request, slot: int, tok: int,
                          now: float, score) -> None:
        self._last_tokens = self._last_tokens.at[slot].set(tok)
        self._emit_first_token(req, slot, tok, now, score)

    def _emit_first_token(self, req: Request, slot: int, tok: int,
                          now: float, score) -> None:
        """First-token bookkeeping shared by BOTH admission paths (dense
        batched prefill, mixed tick's finished chunks), so TTFT/prefill
        accounting can never drift between them.  ``score`` is the (2,)
        [logprob, entropy] row the in-dispatch sampler computed for ``tok``.
        """
        req.slot = slot
        req.tokens.append(tok)
        req.scores.append(float(score[0]))
        req.entropies.append(float(score[1]))
        if req.first_token_s is None:
            # a replayed (failed-over) request keeps its ORIGINAL first-token
            # time: re-prefilling on the sibling is recovery, not a prefill
            # the client observed twice
            req.first_token_s = now
            self.stats.ttft_s.append(now - req.arrived_s)
        self.stats.prefills += 1
        self.stats.tokens_out += 1
        if len(req.tokens) >= req.max_new_tokens:
            self._release_slot(slot, req)              # done at first token
            self._complete(req)
        else:
            self.live[slot] = req

    def _release_slot(self, slot: int, req: Request) -> None:
        if self.paged:
            # a replayed request's first replay_offset tokens were folded
            # into the prompt; only the rest are "generated" here, so the
            # trie caches each written position exactly once
            gen = (req.tokens[req.replay_offset:] if req.replay_offset
                   else req.tokens)
            self.cm.finish(slot, gen)
        else:
            self.cm.release(slot)

    def _complete(self, req: Request) -> None:
        req.done_s = time.monotonic()
        if self.spill_pool is not None:
            # a preempted request reaching ANY terminal state (done, expired
            # in queue, rejected) must not leak its parked KV
            self.spill_pool.discard(req.request_id)
        if self.on_complete is not None:
            self.on_complete(req)

    def _record_issue(self, req: Request) -> None:
        """Queue-wait bookkeeping at FIRST issue (slot granted): a preempted
        request keeps its original issue time — its wait was observed once."""
        if req.issued_s is None:
            req.issued_s = time.monotonic()
            self.stats.queue_wait_s.setdefault(req.slo, []).append(
                req.issued_s - req.arrived_s)

    # ================================================== unified paged tick
    def _pack_chunk(self, slot: int, toks: np.ndarray, pos: np.ndarray,
                    rows: np.ndarray, sample_idx: np.ndarray, n: int,
                    finished: list[int]) -> int:
        """Pack the next prompt chunk of ``slot`` into lanes [n, n+take) —
        at most the budget remainder — and commit newly covered full blocks
        to the trie so same-tick later admissions can share them."""
        seq = self.cm.slots[slot]
        take = min(self.token_budget - n, len(seq.prompt) - seq.prefill_pos)
        if take <= 0:
            return n
        start = seq.prefill_pos
        toks[n:n + take] = seq.prompt[start:start + take]
        pos[n:n + take] = np.arange(start, start + take, dtype=np.int32)
        rows[n:n + take] = slot
        n += take
        self.stats.prefill_tokens += take
        self.stats.prefill_chunks += 1
        if self.cm.commit_prefill_progress(slot, start + take):
            sample_idx[slot] = n - 1       # boundary: the last prompt token
            finished.append(slot)
        return n

    def _admit_mixed(self, toks: np.ndarray, pos: np.ndarray,
                     rows: np.ndarray, sample_idx: np.ndarray, n: int,
                     finished: list[int]) -> int:
        """Admit queue heads one at a time while budget and slots remain;
        each admission immediately packs its first chunk, so the per-token
        budget — not a request count — bounds this tick's prefill work."""
        free = self.cm.n_slots - self.cm.n_active
        while n < self.token_budget and free > 0:
            req = self.scheduler.admit_one(
                self.replica_id, free_slots=free,
                free_blocks=self.cm.available_for_admission(),
                block_cost=self._block_cost,
                max_blocks=self.cm.num_blocks - 1)
            if req is None:
                break
            if self.spill_pool is not None and req.tokens:
                # resume path: a preempted request re-issuing.  Restore its
                # parked KV via adopt (the slot decodes again from the NEXT
                # tick — this tick packs nothing for it, so no lane math
                # changes); when the pool evicted the entry, fall through to
                # prompt replay below.
                parked = self.spill_pool.unpark(req.request_id)
                if parked is not None and self.adopt(req, parked):
                    self.stats.resumes += 1
                    self._record_issue(req)
                    free -= 1
                    continue
            if len(req.tokens) > req.replay_offset:
                # preempted emissions whose parked KV is gone (evicted, or
                # adopt couldn't place it): fold them into the prompt so
                # replay-prefill reproduces the stream exactly
                if not req.fold_for_replay():
                    self._reject(req, "cannot replay preempted request: "
                                      "embeds prompt")
                    continue
            err = self._validate(req)
            if err is not None:
                # unservable request enqueued behind submit()'s back (e.g.
                # straight into the scheduler): reject it through the
                # completion path, keep admitting
                self._reject(req, err)
                continue
            p = self._norm_prompt(req.prompt)
            slot = self.cm.acquire(req.request_id)
            seq = (self.cm.begin(slot, p, req.max_new_tokens)
                   if slot is not None else None)
            if seq is None:
                # slot/block accounting drift: put the head back and retry
                # next tick — admitting younger arrivals now would reorder a
                # FIFO session's turns
                self.scheduler.requeue(self.replica_id, req)
                break
            if req.replay_offset:
                # begin() reserved for the folded prompt as if every token
                # were fresh; the replayed footprint is the uninterrupted
                # request's (see _block_cost) — correct it so admission
                # headroom stays exact across a failover
                seq.reserve = self._block_cost(req)
            self._record_issue(req)
            free -= 1
            self.stats.prompt_tokens += len(p)
            self.stats.prefix_hit_tokens += seq.reused
            if seq.reused:
                self.stats.prefix_hits += 1
            self.prefilling[slot] = req
            n = self._pack_chunk(slot, toks, pos, rows, sample_idx, n,
                                 finished)
        return n

    def _plan_drafts(self, decode_slots: list[int], lanes_left: int
                     ) -> dict[int, list[int]]:
        """Per live slot, the draft tokens to verify this tick.

        Token-budget audit: a speculative row packs 1 + len(drafts) tokens,
        so the old "every decode row costs exactly one token" arithmetic
        would oversubscribe the fixed packed shape.  Draft lanes are
        therefore granted LAST, from ``lanes_left`` — the lanes still idle
        after every live row's mandatory token AND all prefill chunk work
        has packed — so a k-token row can never exceed token_budget, never
        starves a sibling decode row of its mandatory lane, and never
        delays a waiting prefill (TTFT sees exactly the budget the
        non-speculative tick would give it; speculation only monetizes
        lanes that would have been pads).  Drafts are further capped at
        max_new - generated - 1: a fully-accepted row emits drafts + one
        bonus token, so this cap keeps every emission within max_new AND
        every draft KV write within ``written_max`` (the admission
        block-budget rule — speculation never writes a position plain
        decode would not eventually write)."""
        plans: dict[int, list[int]] = {}
        if not self.spec_k:
            return plans
        for slot in decode_slots:
            if lanes_left <= 0:
                break
            req = self.live[slot]
            room = req.max_new_tokens - len(req.tokens) - 1
            m = min(self.spec_k, room, lanes_left)
            if m <= 0:
                continue

            def history(req=req):
                # built only if a source asks (the cascade draft never does)
                return np.concatenate([self._norm_prompt(req.prompt),
                                       np.asarray(req.tokens, np.int64)])

            drafts = self.draft_source.propose(req, history, m)[:m]
            # keep only a valid prefix: one out-of-vocab guess invalidates
            # everything the drafter chained after it
            valid: list[int] = []
            for t in drafts:
                if not 0 <= int(t) < self.cfg.vocab_size:
                    break
                valid.append(int(t))
            if valid:
                plans[slot] = valid
                lanes_left -= len(valid)
        return plans

    # ------------------------------------------------- preemption (opt-in)
    def _maybe_preempt(self) -> None:
        """Tick-entry pressure check: when the best waiting request (the one
        the next issue would pick) cannot issue for lack of slots/blocks,
        evict AT MOST ONE in-flight victim whose virtual deadline is
        strictly later — EDF applied across the issue boundary.  One victim
        per tick keeps the policy damped (no convoys of spills from a
        single burst) and bounds the extra sync cost at one spill/tick."""
        waiter = self.scheduler.best_waiting(self.replica_id)
        if waiter is None:
            return
        need = self._block_cost(waiter)
        if need > self.cm.num_blocks - 1:
            return                    # unservable: the rejection path's job
        if (self.cm.n_slots - self.cm.n_active > 0
                and need <= self.cm.available_for_admission()):
            return                    # will issue normally this tick
        w_vdl = virtual_deadline(waiter)
        victim_slot, victim, v_vdl = None, None, w_vdl
        for slot, req in list(self.prefilling.items()) + list(self.live.items()):
            if req.session_key == waiter.session_key:
                continue              # same session: waiter can't overtake
            vdl = virtual_deadline(req)
            if vdl > v_vdl:
                victim_slot, victim, v_vdl = slot, req, vdl
        if victim is not None:
            self.preempt_slot(victim_slot, victim)

    def preempt_slot(self, slot: int, req: Request) -> None:
        """Evict one in-flight request and requeue it at the head of its
        queue (per-session order preserved — it is again the oldest waiting
        entry of its session).

        Mid-prefill victims release their blocks and replay from the prompt
        — nothing was emitted, so replay is exact and free.  Decoding
        victims spill their KV through the ONE sync site (counted in
        ``spill_syncs``: a preempting tick satisfies ``host_syncs == ticks
        + spill_syncs``; non-preempting ticks keep the strict equality) and
        park it in the spill pool; if the park fails — no pool, pool too
        small — the emissions fold into the prompt NOW so the eventual
        re-issue replays the stream bit-identically."""
        if slot in self.prefilling:
            self.prefilling.pop(slot)
            self.cm.release(slot)
        else:
            self.live.pop(slot)
            # no pool to park into → skip the spill entirely (and its sync):
            # the emissions fold for replay below, and host_syncs == ticks
            # stays strict on a pool-less preempting engine
            spilled = self.spill(slot) if self.spill_pool is not None else None
            self.cm.release(slot)
            parked = (spilled is not None
                      and self.spill_pool.park(req.request_id, spilled,
                                               spilled.n_blocks))
            if not parked:
                req.fold_for_replay()   # paged prompts are tokens: can't fail
        req.slot = None
        self.stats.preemptions += 1
        self.scheduler.requeue(self.replica_id, req)

    def lower_tick(self):
        """Lower the unified tick at this engine's fixed operand shapes, with
        nothing dispatched or donated: ``lower_tick().compile()`` gives the
        program every tick runs (its HLO, memory analysis, compile time)."""
        n, K, T = self.cm.n_slots, self.spec_k, self.token_budget
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
        return self._mixed.lower(
            self.params, self.cm.pools, i32(n, self.cm.max_blocks), i32(T),
            i32(T), i32(T), i32(n, K + 1), i32(n, K), i32(n), i32())

    def _tick_mixed(self) -> int:
        """ONE fixed-shape mixed step: decode rows (each with up to spec_k
        verified draft tokens), + prefill chunks packed against the token
        budget, one dispatch, one host sync."""
        if self.preempt:
            self._maybe_preempt()
        T = self.token_budget
        K = self.spec_k
        toks = np.zeros(T, np.int32)
        pos = np.full(T, -1, np.int32)
        rows = np.full(T, -1, np.int32)
        sample_idx = np.zeros((self.cm.n_slots, K + 1), np.int32)
        draft_toks = np.zeros((self.cm.n_slots, K), np.int32)
        draft_len = np.zeros(self.cm.n_slots, np.int32)
        finished: list[int] = []
        n = 0
        decode_slots = list(self.live.keys())
        # 0. grow live rows' tables to cover the position each is about to
        #    write — BEFORE packing, while prefilling slots still sit at
        #    pos=0 (a chunk that completes its prompt this tick sets pos=S,
        #    but its first decode write is next tick's business); draft
        #    positions get their own ensure in step 4
        self.cm.ensure_decode_blocks()
        # 1. every live decode row costs one token (budget >= n_slots, so
        #    decodes can never be starved by prefill chunks)
        for slot in decode_slots:
            seq = self.cm.slots[slot]
            toks[n] = self._last_host[slot]
            pos[n] = seq.pos
            rows[n] = slot
            sample_idx[slot] = n                  # all entries → base lane
            n += 1
        # 2. continue partial prefills in admission order (FIFO turns stay
        #    ordered: an older request's chunks always pack first)
        for slot in list(self.prefilling):
            if n >= T:
                break
            n = self._pack_chunk(slot, toks, pos, rows, sample_idx, n,
                                 finished)
        # 3. admit new requests into the remainder
        n = self._admit_mixed(toks, pos, rows, sample_idx, n, finished)
        # 4. draft tokens fill the lanes NOTHING else wanted (they would
        #    have dispatched as pads): row slot's drafts verify positions
        #    pos+1..pos+m.  Lane order does not matter — the kernel masks
        #    by POSITION and writes all packed K/V before any read — so a
        #    row's draft lanes need not be contiguous with its base lane.
        plans = self._plan_drafts(decode_slots, T - n)
        if plans:
            # grow ONLY the planned rows: by now a slot whose prompt just
            # completed sits at pos = S, and growing it would claim a
            # decode block its admission budget never reserved
            self.cm.ensure_decode_blocks(
                {s: len(d) for s, d in plans.items()}, only=set(plans))
            for slot, drafts in plans.items():
                seq = self.cm.slots[slot]
                m = len(drafts)
                toks[n:n + m] = drafts
                pos[n:n + m] = np.arange(seq.pos + 1, seq.pos + 1 + m)
                rows[n:n + m] = slot
                sample_idx[slot, 1:1 + m] = np.arange(n, n + m)
                draft_toks[slot, :m] = drafts
                draft_len[slot] = m
                self.stats.spec_drafted += m
                n += m
        if n == 0:
            return 0          # idle: nothing dispatched, not a tick
        t0 = time.monotonic()
        bt = jnp.asarray(self.cm.block_tables())       # (n_slots, max_blocks)
        sampled, n_acc, scores, pools = self._mixed(
            self.params, self.cm.pools, bt, jnp.asarray(toks),
            jnp.asarray(pos), jnp.asarray(rows), jnp.asarray(sample_idx),
            jnp.asarray(draft_toks), jnp.asarray(draft_len),
            self._next_seed())
        self.cm.pools = pools
        self.cm.publish()
        self.stats.blocks_in_use = self.cm.blocks_in_use
        # the ONE sync of this tick: tokens + accept counts + scores in one
        # device_get — speculation amortizes it over every accepted token
        host_toks, host_acc, host_scores = self._to_host(
            (sampled, n_acc, scores))
        dt = time.monotonic() - t0
        now = time.monotonic()
        n_emitted = 0
        # 4. decode rows advance: the accepted draft prefix plus the
        #    correction/bonus token all land this tick
        for slot in decode_slots:
            req = self.live[slot]
            seq = self.cm.slots[slot]
            m = int(draft_len[slot])
            a = int(host_acc[slot])
            n_emit = a + 1
            for j in range(n_emit):
                req.tokens.append(int(host_toks[slot, j]))
                req.scores.append(float(host_scores[slot, j, 0]))
                req.entropies.append(float(host_scores[slot, j, 1]))
                self.stats.tpot_s.append(dt / n_emit)
            self._last_host[slot] = int(host_toks[slot, a])
            seq.pos += n_emit
            self.stats.tokens_out += n_emit
            n_emitted += n_emit
            if m:
                self.stats.spec_accepted += a
                if a < m:
                    # KV written for the rejected tail (positions >= the new
                    # seq.pos) is rolled back: table truncated, tail blocks
                    # freed, trie untouched (see kvcache.rollback_writes)
                    self.stats.spec_rolled_back += m - a
                    self.cm.rollback_writes(slot, seq.pos)
            if len(req.tokens) >= req.max_new_tokens:
                self.live.pop(slot)
                self._release_slot(slot, req)
                self._complete(req)
        # 5. chunks that completed their prompt emit their first token
        for slot in finished:
            req = self.prefilling.pop(slot)
            tok = int(host_toks[slot, 0])
            self._last_host[slot] = tok
            n_emitted += 1
            self._emit_first_token(req, slot, tok, now, host_scores[slot, 0])
        self.stats.ticks += 1
        if decode_slots:
            self.stats.decode_ticks += 1
        return n_emitted

    # ----------------------------------------------------- dense decode tick
    def _tick_dense(self) -> int:
        self._admit_dense()
        if not self.live:
            self.stats.ticks += 1
            return 0
        t0 = time.monotonic()
        positions = self.cm.positions()[:, None]               # (B,1)
        active = self.cm.active_mask()
        new_toks, step_scores, self.cm.caches = self._step(
            self.params, self.cm.caches, self._last_tokens, positions,
            active, self._next_seed())
        self._last_tokens = new_toks
        # the ONE sync of this tick: tokens + scores in one device_get
        host_toks, host_scores = self._to_host((new_toks, step_scores))
        self.cm.advance()
        dt = time.monotonic() - t0
        done = []
        n_emitted = 0
        for slot, req in list(self.live.items()):
            req.tokens.append(int(host_toks[slot]))
            req.scores.append(float(host_scores[slot, 0]))
            req.entropies.append(float(host_scores[slot, 1]))
            n_emitted += 1
            self.stats.tpot_s.append(dt)
            if len(req.tokens) >= req.max_new_tokens:
                done.append(slot)
        for slot in done:
            req = self.live.pop(slot)
            self._release_slot(slot, req)
            self._complete(req)
        self.stats.ticks += 1
        self.stats.decode_ticks += 1
        self.stats.tokens_out += n_emitted
        return n_emitted

    def tick(self) -> int:
        """One engine step.  Paged: one unified mixed dispatch (decode rows +
        prefill chunks).  Dense: admit prefills, then decode all live slots.

        Fault seams fire at tick ENTRY — before any dispatch — so the pool
        is never mid-donation when a fault lands: a crash raises
        ``ReplicaCrashed`` (the node marks the replica down and evacuates),
        a stall returns 0 without progress (only the deployment watchdog
        can see it), a slow tick sleeps then proceeds (deadlines, not
        failover, handle it).
        """
        if self.crashed:
            raise ReplicaCrashed(f"replica {self.replica_id} is marked down")
        if self.faults is not None:
            if self.faults.on_tick(self) == "stall":
                return 0
        self._sweep_deadlines()
        if self.paged:
            return self._tick_mixed()
        return self._tick_dense()

    # --------------------------------------- spill (failover + preemption)
    def spill(self, slot: int) -> SpilledKV | None:
        """Spill one live slot's KV blocks to host (driver thread): on a
        replica being marked down (failover), or on a preemption victim
        being evicted for a higher-priority waiter.  The device-side gather
        happens in the cache manager; the ONE host transfer goes through
        ``_to_host`` — the same sanctioned sync site as the tick pull — and
        is counted in ``spill_syncs`` so the invariant on a spilling
        replica is ``host_syncs == ticks + spill_syncs`` (replicas that
        never spill keep the strict ``host_syncs == ticks``)."""
        if not self.paged:
            return None
        seq = self.cm.slots[slot]
        if not seq.active or not seq.table:
            return None
        host_blocks = self._to_host(self.cm.spill_device(slot))
        self.stats.spill_syncs += 1
        self.stats.spilled_sessions += 1
        self.stats.spilled_blocks += len(seq.table)
        return SpilledKV(request_id=seq.request_id, pos=seq.pos,
                         n_blocks=len(seq.table),
                         block_size=self.cm.block_size, blocks=host_blocks)

    def evacuate(self, *, spill_kv: bool = True
                 ) -> tuple[list[Request], list[tuple[Request, Any]]]:
        """Empty a dead replica (driver thread only, after ``crashed`` is
        set so racing submits bounce): queued requests pop for plain
        resubmission; mid-prefill requests release their blocks (replay is
        exact — nothing was emitted); live requests spill their KV when
        ``spill_kv`` (else, or on spill failure, they re-home as replays).
        Every slot is released here, so the allocator ends exactly where a
        normal drain would leave it.  Returns (queued, [(req, spilled)])."""
        queued = self.scheduler.drain(self.replica_id)
        inflight: list[tuple[Request, Any]] = []
        for slot, req in list(self.prefilling.items()):
            self.prefilling.pop(slot)
            self.cm.release(slot)
            inflight.append((req, None))
        for slot, req in list(self.live.items()):
            self.live.pop(slot)
            spilled = None
            if spill_kv:
                try:
                    spilled = self.spill(slot)
                except Exception:
                    spilled = None       # unrecoverable KV: replay instead
            self.cm.release(slot)
            inflight.append((req, spilled))
        return queued, inflight

    def adopt(self, req: Request, spilled: SpilledKV | None) -> bool:
        """Restore a sibling's spilled session into this replica: allocate
        fresh blocks, scatter the migrated KV in, resume decoding at the
        spilled position — the client-visible stream continues exactly
        where the dead replica left it (greedy decoding is bit-identical
        to the uninterrupted run).  False (nothing allocated) when this
        replica can't host it; the caller falls back to prompt replay."""
        if (not self.paged or self.crashed or spilled is None
                or not req.tokens):
            return False
        slot = self.cm.acquire(req.request_id)
        if slot is None:
            return False
        seq = self.cm.adopt(slot, self._norm_prompt(req.prompt), spilled,
                            req.max_new_tokens)
        if seq is None:
            return False                 # cm.adopt released the slot
        self._last_host[slot] = int(req.tokens[-1])
        req.slot = slot
        self.live[slot] = req
        self.stats.adopted_sessions += 1
        return True

    def run_until_drained(self, max_ticks: int = 10_000) -> None:
        for _ in range(max_ticks):
            if self.idle():
                return
            self.tick()
        raise TimeoutError("engine did not drain")
