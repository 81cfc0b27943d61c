"""The one traffic generator: every mix is a file of parameters it reads.

A mix describes sessions.  Each session is a client that sends turns one
after another: a turn's prompt is an optional tenant system prompt, then the
session's history so far (when ``history`` is on), then fresh user tokens;
the next turn is due when the answer has arrived plus a think time.  How
sessions start is the arrival process:

- ``"poisson"``: open loop, sessions start at ``rate_per_s`` (independent
  users: the queue can grow);
- ``"closed"``: ``clients`` sessions with unbounded turns, their starts
  spread evenly over ``stagger_s`` (callers that wait on each reply).

The work is a fixed schedule of ``pool`` session plans: each plan holds a
tenant, a number of turns and, for each of its first ``span`` turns, the
user tokens, the answer's length and the think time.  Every size and every
gap between session starts comes from a population of values at the
distribution's quantiles ``(i + 0.5) / n``, dealt to the plans in one
fixed order; session ``k`` follows plan ``k`` (wrapping round).  The seed
draws the token ids, so every seed offers the same work with other
contents: a run's spread is that of the system, not of the draw.  (Seeds
that also reordered the plans moved a window of some tens of requests by
far more than any bound can hold; see PERF.md.)

Distributions (each a JSON object with ``dist``):
``fixed`` (value), ``lognormal`` (median, sigma, min, max), ``exponential``
(mean), ``geometric`` (mean, max; support 1, 2, ...), ``unbounded`` (turns
only: a session that never ends).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from statistics import NormalDist

import numpy as np


def quantile(spec: dict, u: float) -> float:
    """The value of distribution ``spec`` at cumulative probability u."""
    kind = spec["dist"]
    if kind == "fixed":
        return float(spec["value"])
    if kind == "lognormal":
        z = NormalDist().inv_cdf(u)
        x = math.exp(math.log(spec["median"]) + spec["sigma"] * z)
        return min(max(x, spec.get("min", -math.inf)),
                   spec.get("max", math.inf))
    if kind == "exponential":
        return -spec["mean"] * math.log1p(-u)
    if kind == "geometric":
        p = 1.0 / spec["mean"]
        x = 1 if p >= 1 else math.ceil(math.log1p(-u) / math.log1p(-p))
        return float(min(max(x, 1), spec.get("max", math.inf)))
    raise ValueError(f"unknown distribution {kind!r}")


def population(spec: dict, n: int, *, integer: bool = False) -> np.ndarray:
    """``n`` values at the quantiles (i + 0.5) / n of ``spec``, in order."""
    vals = [quantile(spec, (i + 0.5) / n) for i in range(n)]
    return np.asarray([round(v) for v in vals] if integer else vals,
                      np.int64 if integer else np.float64)


def zipf_population(count: int, s: float, n: int) -> np.ndarray:
    """``n`` tenant ids in [0, count) in the proportions of Zipf(s)."""
    w = 1.0 / np.arange(1, count + 1) ** s
    cdf = np.cumsum(w / w.sum())
    u = (np.arange(n) + 0.5) / n
    return np.minimum(np.searchsorted(cdf, u), count - 1)


def _dealt(values: np.ndarray, rng: np.random.Generator) -> np.ndarray:
    """A population in the fixed order it is dealt to the plans."""
    return values[rng.permutation(len(values))]


@dataclass
class Session:
    index: int
    plan: int
    start_s: float            # offset from the start of the traffic
    tenant: int | None
    turns: int | None         # None: unbounded
    history: np.ndarray = field(default_factory=lambda: np.zeros(0, np.int32))
    sent: int = 0


@dataclass
class Turn:
    request_id: str
    session_key: str
    prompt: np.ndarray
    max_new_tokens: int


class Mix:
    """The sessions, turns and think times of one mix under one seed.

    What a turn holds depends only on the seed and on which session and turn
    it is, never on when the system answered: session ``k`` follows plan
    ``k % pool``, and its token ids come from a stream keyed by (seed, k,
    turn)."""

    def __init__(self, params: dict, seed: int, *, vocab: int, max_len: int):
        self.vocab, self.max_len = vocab, max_len
        # any whole number seeds it, wider than 64 bits or negative too
        self.seed = int(seed) % 2**128
        n = self.n_plans = int(params["pool"])
        arr = params["sessions"]
        self.closed = arr["arrival"] == "closed"
        turns = params["turns"]
        self.span = span = int(params["span"])
        deal = np.random.default_rng(0)      # the same schedule for every seed
        if self.closed:
            k = int(arr["clients"])
            self.starts = list(np.arange(k) * float(arr["stagger_s"]) / k)
        else:
            self.gaps = _dealt(population(
                {"dist": "exponential", "mean": 1.0 / arr["rate_per_s"]}, n),
                deal)
            self.t_next = 0.0
        ten = params.get("tenants")
        self.tenant = (_dealt(zipf_population(ten["count"], ten["zipf_s"], n),
                              deal) if ten else None)
        self.system = [self._ids(("system", t), ten["system_tokens"])
                       for t in range(ten["count"])] if ten else []
        self.turns = (None if turns["dist"] == "unbounded"
                      else _dealt(population(turns, n, integer=True), deal))
        per_turn = lambda spec, **kw: _dealt(  # noqa: E731
            population(spec, n * span, **kw), deal).reshape(n, span)
        self.user = per_turn(params["user_tokens"], integer=True)
        self.output = per_turn(params["output_tokens"], integer=True)
        self.think = per_turn(params["think_s"])
        self.history = bool(params.get("history", False))
        self.margin = int(params.get("context_margin", 0))
        self.n_sessions = 0

    def _ids(self, key: tuple, n: int) -> np.ndarray:
        words = [ord(c) for c in key[0]] + list(key[1:])
        ss = np.random.SeedSequence([self.seed, 1, *words])
        return np.random.default_rng(ss).integers(0, self.vocab, n,
                                                  dtype=np.int32)

    def next_session(self) -> Session | None:
        """The next session to start, or None when a closed loop has started
        all its clients."""
        k = self.n_sessions
        if self.closed:
            if k >= len(self.starts):
                return None
            start = float(self.starts[k])
        plan = k % self.n_plans
        if not self.closed:
            start = self.t_next
            self.t_next += float(self.gaps[plan])
        self.n_sessions += 1
        return Session(index=k, plan=plan, start_s=start,
                       tenant=(None if self.tenant is None
                               else int(self.tenant[plan])),
                       turns=(int(self.turns[plan]) if self.turns is not None
                              else None))

    def next_turn(self, s: Session) -> Turn | None:
        """The session's next turn, or None when it has ended: its turns are
        spent, or its context would pass ``max_len`` less the margin."""
        if s.turns is not None and s.sent >= s.turns:
            return None
        j = s.sent % self.span
        user = self._ids(("turn", s.index, s.sent), int(self.user[s.plan, j]))
        out = int(self.output[s.plan, j])
        head = (self.system[s.tenant] if s.tenant is not None
                else np.zeros(0, np.int32))
        prompt = (np.concatenate([head, s.history, user]) if self.history
                  else np.concatenate([head, user]))
        if len(prompt) + out > self.max_len - self.margin:
            return None
        s.sent += 1
        return Turn(request_id=f"s{s.index}t{s.sent}",
                    session_key=f"s{s.index}", prompt=prompt,
                    max_new_tokens=out)

    def answered(self, s: Session, turn: Turn, tokens) -> float:
        """Record a turn's answer; returns the think time before the next."""
        if self.history:
            # the system prompt stays at the head of every turn; the history
            # is what follows it
            skip = len(self.system[s.tenant]) if s.tenant is not None else 0
            s.history = np.concatenate([turn.prompt[skip:],
                                        np.asarray(tokens, np.int32)])
        return float(self.think[s.plan, (s.sent - 1) % self.span])
