"""The program's own phases in a traced run: device scopes, host spans,
attention-grid counts, and each idle gap put down to a phase.

``harness.trace`` reduces a trace to busy time, per-op self times and idle
gaps named by the benchmark's spans.  This adds what the program itself
puts in the trace, and leaves those numbers as they are (``reduce`` calls
``trace.reduce`` on the same planes):

- A device op's program scope is the ``op_name`` metadata of its HLO
  instruction.  A TPU trace's op events carry the instruction's name and
  text without its metadata, so the scopes come from the compiled tick's
  HLO text (``op_names``); an op counts as the tick's when it runs inside
  one of the tick module's events on the ``XLA Modules`` line.  The scope
  is the op_name's named scopes, without the JAX wrappers (``jit(f)``,
  ``while/body``, einsum specs) and the primitive: ``kv_pool/layout``.
- Host spans are the program's ``cascade.*`` and the benchmark's
  ``bench.*`` events, with their arguments; ``cascade.tick.dispatch``
  carries the tick's attention-grid counts.
- Each idle gap of device 0 is split over the innermost span at each
  instant (the deepest on its thread, the latest started on a tie; ``host``
  where none) and named by the span that holds most of it.

``load`` turns an ``.xplane.pb`` file (and the tick's HLO text) into plain
data, which ``record`` shrinks for a checked-in test trace; ``reduce`` works
on the plain data alone.
"""
from __future__ import annotations

import bisect
import re
from dataclasses import dataclass, field

from . import trace

MODULES_LINE = "XLA Modules"
HOST_PREFIXES = ("bench.", "cascade.")
DISPATCH_SPAN = "cascade.tick.dispatch"
UNSCOPED = ""                       # a tick op with no program scope
OTHER = "(other programs)"          # an op outside the tick's module
# name-stack segments JAX adds around a program's own scopes
_WRAPPERS = {"while", "body", "cond", "closed_call", "shard_map",
             "checkpoint", "remat", "pjit"}
_SCOPE = re.compile(r"^[A-Za-z_][A-Za-z0-9_]*$")
_INSTR = re.compile(r'^\s*(?:ROOT )?%([^\s=]+) = (.*)$', re.M)
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def op_names(hlo_text: str) -> tuple[str, dict]:
    """(module name, {instruction name: op_name}) of a compiled program's
    HLO text; "" for an instruction without one (a copy XLA put in)."""
    module = re.search(r"^HloModule ([^\s,]+)", hlo_text, re.M).group(1)
    names = {}
    for m in _INSTR.finditer(hlo_text):
        op = _OP_NAME.search(m.group(2))
        names[m.group(1)] = op.group(1) if op else ""
    return module, names


def scope_of(op_name: str) -> str:
    """The named scopes of an op_name, outermost first, without JAX's
    wrappers and the primitive's own name."""
    parts = op_name.split("/")[:-1]
    return "/".join(p for p in parts
                    if _SCOPE.match(p) and p not in _WRAPPERS)


def load(path: str, hlo_text: str | None = None) -> dict:
    """The planes of an ``.xplane.pb`` file as plain data, with each host
    span's arguments, and the tick's op_names when its HLO text is given.

    ``planes`` is ``trace.load``'s format (events are (name, start_ns,
    dur_ns)); a host line also holds ``args``, one dict or None per event.
    """
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    planes = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            events, args = [], []
            for e in line.events:
                events.append((e.name.split(" = ", 1)[0].lstrip("%"),
                               int(e.start_ns), int(e.duration_ns)))
                args.append(dict(e.stats) if e.name.startswith(
                    HOST_PREFIXES) else None)
            lines.append({"name": line.name, "events": events,
                          "args": args})
        planes.append({"name": plane.name, "lines": lines})
    module, names = op_names(hlo_text) if hlo_text else (None, {})
    return {"planes": planes, "module": module, "op_names": names}


def record(rec: dict) -> dict:
    """The part of ``load``'s data that ``reduce`` reads, small enough to
    check in: the devices' ``XLA Ops`` and ``XLA Modules`` lines, the host
    spans of ``HOST_PREFIXES`` with their arguments, and the op_names of
    the ops that ran."""
    planes, ran = [], set()
    for p in rec["planes"]:
        device = trace._DEVICE.match(p["name"])
        lines = []
        for ln in p["lines"]:
            if device and ln["name"] in (trace.OPS_LINE, MODULES_LINE):
                lines.append({"name": ln["name"],
                              "events": [list(e) for e in ln["events"]]})
                if ln["name"] == trace.OPS_LINE:
                    ran.update(e[0] for e in ln["events"])
            elif not device:
                keep = [i for i, e in enumerate(ln["events"])
                        if e[0].startswith(HOST_PREFIXES)]
                if keep:
                    lines.append({
                        "name": ln["name"],
                        "events": [list(ln["events"][i]) for i in keep],
                        "args": [ln["args"][i] for i in keep]})
        if lines:
            planes.append({"name": p["name"], "lines": lines})
    return {"planes": planes, "module": rec["module"],
            "op_names": {n: o for n, o in rec["op_names"].items()
                         if n in ran}}


@dataclass
class Phases:
    base: trace.Reduced                 # trace.reduce of the same planes
    scope_s: dict = field(default_factory=dict)    # scope -> s/device
    mapped: float = 0.0                 # share of the tick's op time named
    span_s: dict = field(default_factory=dict)     # span -> s in window
    span_n: dict = field(default_factory=dict)     # span -> events
    counts: dict = field(default_factory=dict)     # summed dispatch args
    idle_s: dict = field(default_factory=dict)     # span -> idle s, dev 0
    idle_gaps: list = field(default_factory=list)  # [[span, s]], longest

    def share(self, scope: str) -> float:
        """Self time of the ops under ``scope`` (a scope segment), as a
        share of device busy time (0-1)."""
        t = sum(s for k, s in self.scope_s.items()
                if scope in k.split("/"))
        return t / self.base.busy_s if self.base.busy_s > 0 else 0.0

    def ticks(self) -> int:
        return self.span_n.get(DISPATCH_SPAN, 0)


def _host_spans(planes) -> list:
    """(name, start, end, depth, args) of every host span but the window,
    with its nesting depth on its own thread."""
    out = []
    for p in planes:
        if trace._DEVICE.match(p["name"]):
            continue
        for ln in p["lines"]:
            args = ln.get("args") or [None] * len(ln["events"])
            evs = sorted(((n, s, s + d, a) for (n, s, d), a
                          in zip(ln["events"], args)
                          if n.startswith(HOST_PREFIXES)
                          and n != trace.WINDOW_SPAN),
                         key=lambda e: (e[1], -e[2]))
            stack: list = []
            for n, a, b, arg in evs:
                while stack and stack[-1] <= a:
                    stack.pop()
                out.append((n, a, b, len(stack), arg))
                stack.append(b)
    return out


def _gaps(ops, lo: int, hi: int) -> list:
    gaps, prev = [], lo
    for a, b in trace._union((a, b) for _, a, b in ops) + [(hi, hi)]:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    return gaps


def _split(a: int, b: int, spans) -> dict:
    """Nanoseconds of [a, b) under each innermost span, or ``host`` where
    no span covers it."""
    cuts = sorted({a, b} | {t for _, s, e, _, _ in spans
                            for t in (s, e) if a < t < b})
    out: dict = {}
    for x, y in zip(cuts, cuts[1:]):
        live = [sp for sp in spans if sp[1] <= x and sp[2] >= y]
        name = (max(live, key=lambda sp: (sp[3], sp[1]))[0] if live
                else "host")
        out[name] = out.get(name, 0) + (y - x)
    return out


def _window(planes) -> tuple[int, int]:
    """The traced window as ``trace.reduce`` takes it."""
    win = [(s, s + d) for p in planes for ln in p["lines"]
           for n, s, d in ln["events"] if n == trace.WINDOW_SPAN]
    if win:
        return win[0]
    evs = [e for p in planes if trace._DEVICE.match(p["name"])
           for ln in p["lines"] if ln["name"] == trace.OPS_LINE
           for e in ln["events"]]
    return (min(s for _, s, _ in evs), max(s + d for _, s, d in evs))


def _scopes(lines: dict, lo: int, hi: int, module, names) -> tuple:
    """({scope: self ns}, tick ns, tick ns with a known name) of one
    device's ops in the window."""
    ops = list(trace._clip(lines[trace.OPS_LINE], lo, hi))
    tick = trace._union((s, s + d) for n, s, d in lines.get(MODULES_LINE, [])
                        if n.split("(")[0] == module)
    starts = [a for a, _ in tick]
    labelled = []
    for n, a, b in ops:
        i = bisect.bisect_right(starts, a) - 1
        if i < 0 or tick[i][1] < b:
            labelled.append((OTHER, a, b))
        else:
            labelled.append((scope_of(names[n]) if n in names else None,
                             a, b))
    out: dict = {}
    tick_ns = known_ns = 0
    for label, t in trace._self_times(labelled).items():
        if label != OTHER:
            tick_ns += t
            known_ns += t if label is not None else 0
        key = UNSCOPED if label is None else label
        out[key] = out.get(key, 0) + t
    return out, tick_ns, known_ns


def reduce(rec: dict) -> Phases | None:
    """The phases of ``load``'s (or ``record``'s) data; None when the
    trace holds no device ops."""
    planes = rec["planes"]
    base = trace.reduce(planes)
    if base is None:
        return None
    lo, hi = _window(planes)
    devices = [{ln["name"]: ln["events"] for ln in p["lines"]}
               for p in planes if trace._DEVICE.match(p["name"])
               and any(ln["name"] == trace.OPS_LINE for ln in p["lines"])]
    devices = [d for d in devices if d[trace.OPS_LINE]]
    scope_ns: dict = {}
    tick_ns = known_ns = 0
    for lines in devices:
        got, t, k = _scopes(lines, lo, hi, rec.get("module"),
                            rec.get("op_names") or {})
        tick_ns, known_ns = tick_ns + t, known_ns + k
        for key, v in got.items():
            scope_ns[key] = scope_ns.get(key, 0) + v
    spans = _host_spans(planes)
    span_ns: dict = {}
    span_n: dict = {}
    counts: dict = {}
    for n, a, b, _, args in spans:
        if not n.startswith("cascade.") or min(b, hi) <= max(a, lo):
            continue
        span_ns[n] = span_ns.get(n, 0) + min(b, hi) - max(a, lo)
        span_n[n] = span_n.get(n, 0) + 1
        if n == DISPATCH_SPAN and args:
            for k, v in args.items():
                counts[k] = counts.get(k, 0) + int(v)
    spans.sort(key=lambda sp: sp[1])
    starts = [sp[1] for sp in spans]
    longest = max((b - a for _, a, b, _, _ in spans), default=0)
    idle: dict = {}
    named = []
    for a, b in _gaps(list(trace._clip(devices[0][trace.OPS_LINE], lo, hi)),
                      lo, hi):
        near = [sp for sp in spans[bisect.bisect_left(starts, a - longest):
                                   bisect.bisect_left(starts, b)]
                if sp[2] > a]
        split = _split(a, b, near)
        for k, t in split.items():
            idle[k] = idle.get(k, 0) + t
        named.append([max(split, key=split.get), (b - a) / 1e9])
    named.sort(key=lambda g: -g[1])
    n_dev = len(devices)
    return Phases(
        base=base, scope_s={k: v / n_dev / 1e9 for k, v in scope_ns.items()},
        mapped=known_ns / tick_ns if tick_ns else 0.0,
        span_s={k: v / 1e9 for k, v in span_ns.items()}, span_n=span_n,
        counts=counts, idle_s={k: v / 1e9 for k, v in idle.items()},
        idle_gaps=named[:10])
