"""From a profiler trace to device busy time, kernel time and idle gaps.

``load`` turns the ``.xplane.pb`` file that ``jax.profiler`` writes into
plain data: planes, their lines, and events as (name, start_ns, dur_ns).
``reduce`` works on that plain data only, so a small trace recorded on the
chip can be checked in and the arithmetic tested on it.

- The traced window is the host span ``bench.window`` when the trace holds
  one, else the extent of all events.
- A device is a plane named ``/device:<kind>:<n>`` with an ``XLA Ops``
  line; busy time is the union of its op intervals inside the window, and
  ``busy_s`` is averaged over the devices.
- An op's name is its HLO instruction's (``ragged_paged_attention.7``).  Ops
  nest on the ``XLA Ops`` line (a loop holds its body's ops); ``op_s`` sums
  each name's self time, its duration less that of the ops inside it, so
  a reader finds a kernel's time by its name.  Collective time is the union
  of collective ops.
- Each idle gap of device 0 is named by the benchmark span
  (``bench.*``) on the host that covers most of it, or ``host`` when none.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|all-to-all|"
                        r"collective-permute|psum|ppermute", re.I)
_DEVICE = re.compile(r"^/device:[A-Z]+:\d+$")


def load(path: str) -> list[dict]:
    """The planes of an ``.xplane.pb`` file as plain data."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    planes = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            # a device op's event name is its whole HLO instruction; keep
            # the instruction's name
            lines.append({"name": line.name,
                          "events": [(e.name.split(" = ", 1)[0].lstrip("%"),
                                      int(e.start_ns), int(e.duration_ns))
                                     for e in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def _self_times(ops) -> dict:
    """Each name's summed self time over clipped, possibly nested ops."""
    out: dict = {}
    stack: list = []          # [name, end, self]
    for n, a, b in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][1] <= a:
            name, _, own = stack.pop()
            out[name] = out.get(name, 0) + own
        if stack:
            stack[-1][2] -= min(b, stack[-1][1]) - a
        stack.append([n, b, b - a])
    for name, _, own in stack:
        out[name] = out.get(name, 0) + own
    return out


def _union(intervals) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _length(intervals) -> int:
    return sum(b - a for a, b in intervals)


def _clip(events, lo: int, hi: int):
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            yield name, a, b


@dataclass
class Reduced:
    window_s: float
    busy_s: float                       # mean over devices
    devices: int
    op_s: dict = field(default_factory=dict)       # op name -> s/device
    collective_s: float = 0.0           # mean over devices
    top_ops: list = field(default_factory=list)    # [[name, s/device]]
    idle_gaps: list = field(default_factory=list)  # [[span, s]], device 0


def reduce(planes: list[dict]) -> Reduced | None:
    """Busy, per-op, collective and idle time of the traced window; None
    when the trace holds no device ops."""
    host = [(n, s, s + d) for p in planes if not _DEVICE.match(p["name"])
            for line in p["lines"] for n, s, d in line["events"]
            if n.startswith("bench.")]
    devices = [next(line["events"] for line in p["lines"]
                    if line["name"] == OPS_LINE)
               for p in planes if _DEVICE.match(p["name"])
               and any(line["name"] == OPS_LINE for line in p["lines"])]
    devices = [d for d in devices if d]
    if not devices:
        return None
    win = [(s, e) for n, s, e in host if n == WINDOW_SPAN]
    if win:
        lo, hi = win[0]
    else:
        lo = min(s for evs in devices for _, s, _ in evs)
        hi = max(s + d for evs in devices for _, s, d in evs)
    busy, coll, totals = [], [], {}
    for evs in devices:
        ops = list(_clip(evs, lo, hi))
        busy.append(_length(_union((a, b) for _, a, b in ops)))
        coll.append(_length(_union((a, b) for n, a, b in ops
                                   if COLLECTIVE.search(n))))
        for n, t in _self_times(ops).items():
            totals[n] = totals.get(n, 0) + t
    n_dev = len(devices)
    top = sorted(totals.items(), key=lambda kv: -kv[1])[:10]
    spans = [(n, s, e) for n, s, e in host if n != WINDOW_SPAN]
    gaps = []
    prev = lo
    for a, b in _union((a, b) for _, a, b in _clip(devices[0], lo, hi)) + [
            (hi, hi)]:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    named = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:10]:
        cover = {}
        for n, s, e in spans:
            o = min(b, e) - max(a, s)
            if o > 0:
                cover[n] = cover.get(n, 0) + o
        label = max(cover, key=cover.get) if cover else "host"
        named.append([label, (b - a) / 1e9])
    return Reduced(
        window_s=(hi - lo) / 1e9, busy_s=sum(busy) / n_dev / 1e9,
        devices=n_dev,
        op_s={n: v / n_dev / 1e9 for n, v in totals.items()},
        collective_s=sum(coll) / n_dev / 1e9,
        top_ops=[[n, v / n_dev / 1e9] for n, v in top], idle_gaps=named)
