"""Drives a mix through a deployment and keeps what the window produced.

Three threads call into the program, and nothing else does:

- the stepping thread calls ``node.step()`` in a loop, and takes the
  deployment's counters at the window's open and close, between steps;
- the generator thread submits each turn when it falls due
  (``ModelDeployment.submit``);
- a ``dep.on_done`` listener, run by the program on its own threads, keeps
  each finished ``Request`` and schedules the session's next turn.

Each call into the program sits in a ``jax.profiler.TraceAnnotation`` span
(``bench.node_step``, ``bench.submit``, ``bench.listener``), and so does
the stepping thread's wait for work (``bench.step_wait``), so a traced run
can say what the host was doing while the device was idle.
"""
from __future__ import annotations

import heapq
import itertools
import threading
import time
from dataclasses import dataclass

import numpy as np
from jax.profiler import TraceAnnotation

from .traffic import Mix, Session, Turn

# the engine counters the metrics read, by the name ``ModelDeployment.stats``
# gives each ("requests" counts first tokens, i.e. completed prefills)
COUNTERS = {"ticks": "ticks", "tokens_out": "tokens_out",
            "prefills": "requests", "prompt_tokens": "prompt_tokens",
            "prefill_tokens": "prefill_tokens",
            "prefix_hit_tokens": "prefix_hit_tokens",
            "prefill_chunks": "prefill_chunks", "host_syncs": "host_syncs",
            "submitted": "submitted", "completed": "completed"}


def read_counters(dep) -> dict:
    st = dep.stats()
    return {k: st[v] for k, v in COUNTERS.items()}


@dataclass
class Record:
    """One request as the client saw it; ``req`` is the program's finished
    ``Request`` (None while it is in flight)."""
    request_id: str
    session: Session
    turn: Turn
    due: float
    sent: float | None = None
    req: object = None


class Drive:
    def __init__(self, node, dep, mix: Mix, *, warm_s: float,
                 window_s: float):
        self.node, self.dep, self.mix = node, dep, mix
        self.warm_s, self.window_s = float(warm_s), float(window_s)
        self.records: dict[str, Record] = {}
        self._cv = threading.Condition()
        self._heap: list = []
        self._seq = itertools.count()
        self._halt = threading.Event()
        self.closed = threading.Event()      # set at the window's close
        self.opened = threading.Event()      # ... and at its open
        self.open_s = self.close_s = None
        self.counters_open = self.counters_close = None
        self.busy_step_s = 0.0               # busy node.step() time in window
        self.longest_step = (None, 0.0)      # (start, seconds) in window
        self.errors: list[BaseException] = []
        dep.on_done.append(self._on_done)

    # ------------------------------------------------------------ threads
    def start(self) -> None:
        now = time.monotonic()
        self.t0 = now
        self.t_open = now + self.warm_s
        self.t_close = self.t_open + self.window_s
        first = self.mix.next_session()
        if first is not None:
            self._push(now + first.start_s, "start", first)
        self._threads = [threading.Thread(target=self._guard, args=(f,),
                                          name=f"bench-{f.__name__}",
                                          daemon=True)
                         for f in (self._drive, self._generate)]
        for t in self._threads:
            t.start()

    def stop(self) -> None:
        self._halt.set()
        with self._cv:
            self._cv.notify_all()
        for t in self._threads:
            t.join(timeout=60)
            if t.is_alive():
                raise RuntimeError(f"{t.name} did not stop")
        if self.errors:
            raise self.errors[0]

    def _guard(self, fn) -> None:
        try:
            fn()
        except BaseException as e:          # surfaced by stop()
            self.errors.append(e)
            self.closed.set()
            self.opened.set()

    def _drive(self) -> None:
        while not self._halt.is_set():
            now = time.monotonic()
            if self.open_s is None and now >= self.t_open:
                self.counters_open = read_counters(self.dep)
                self.open_s = now
                self.opened.set()
            elif (self.open_s is not None and self.close_s is None
                  and now >= self.t_close):
                self.counters_close = read_counters(self.dep)
                self.close_s = now
                self.closed.set()
            t = time.monotonic()
            with TraceAnnotation("bench.node_step"):
                busy = self.node.step()
            if self.open_s is not None and self.close_s is None:
                dt = time.monotonic() - t
                if busy:
                    self.busy_step_s += dt
                if dt > self.longest_step[1]:
                    self.longest_step = (t, dt)
            if not busy:
                with TraceAnnotation("bench.step_wait"):
                    time.sleep(0.0005)

    def _push(self, due: float, kind: str, session: Session) -> None:
        heapq.heappush(self._heap, (due, next(self._seq), kind, session))

    def _generate(self) -> None:
        while True:
            with self._cv:
                while not self._halt.is_set():
                    now = time.monotonic()
                    if self._heap and self._heap[0][0] <= now:
                        break
                    wait = (self._heap[0][0] - now) if self._heap else 0.05
                    self._cv.wait(timeout=min(wait, 0.05))
                if self._halt.is_set():
                    return
                due, _, kind, s = heapq.heappop(self._heap)
                if due >= self.t_close:
                    continue                 # the window has closed: no sends
                if kind == "start":
                    nxt = self.mix.next_session()
                    if nxt is not None:
                        self._push(self.t0 + nxt.start_s, "start", nxt)
                turn = self.mix.next_turn(s)
                if turn is None:
                    continue                 # the session has ended
                rec = Record(turn.request_id, s, turn, due)
                self.records[turn.request_id] = rec
            rec.sent = time.monotonic()
            with TraceAnnotation("bench.submit"):
                self.dep.submit(turn.session_key, turn.request_id,
                                turn.prompt,
                                max_new_tokens=turn.max_new_tokens)

    def _on_done(self, req) -> None:
        with TraceAnnotation("bench.listener"), self._cv:
            rec = self.records.get(req.request_id)
            if rec is None or rec.req is not None:
                return
            rec.req = req
            think = self.mix.answered(rec.session, rec.turn, req.tokens)
            self._push((req.done_s or time.monotonic()) + think, "turn",
                       rec.session)
            self._cv.notify()

    # ------------------------------------------------------------ results
    def in_window(self) -> list[Record]:
        """Every request due in the window, in order of due time."""
        return sorted((r for r in self.records.values()
                       if self.open_s <= r.due < self.close_s),
                      key=lambda r: r.due)

    def finished_in_window(self) -> list[Record]:
        """Requests that completed without error inside the window."""
        return [r for r in self.records.values()
                if r.req is not None and r.req.error is None
                and r.req.done_s is not None
                and self.open_s <= r.req.done_s < self.close_s]

    def delta(self, key: str) -> int:
        return self.counters_close[key] - self.counters_open[key]


def censored(t: float | None, close: float) -> float:
    """A client time, or the window's close when it had not come by then."""
    return close if t is None else min(t, close)


def pct(xs, q: float) -> float:
    """The q-th percentile (0-100), linear between order statistics."""
    return float(np.percentile(np.asarray(xs, np.float64), q))
