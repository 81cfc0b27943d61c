"""The trace reduction against times counted by hand: on a small trace
recorded on the chip (danube18b.chat, one TPU v5 lite, 0.5 s), and on a
made-up two-device trace with nesting, overlap and a collective."""
import gzip
import json
from pathlib import Path

import pytest

from bench.harness import readers, trace

DATA = Path(__file__).resolve().parent / "data"


def chip_trace():
    return json.loads(gzip.decompress(
        (DATA / "trace_v5e_chat.json.gz").read_bytes()))["planes"]


def covered(intervals) -> int:
    """Length of a union of intervals, by a sweep over their ends."""
    ends = sorted([(a, 1) for a, _ in intervals]
                  + [(b, -1) for _, b in intervals])
    total, depth, last = 0, 0, None
    for t, step in ends:
        if depth > 0:
            total += t - last
        depth += step
        last = t
    return total


def test_chip_trace_busy_idle_kernel_and_collective():
    planes = chip_trace()
    (lo, hi), = [(s, s + d) for p in planes for ln in p["lines"]
                 for n, s, d in ln["events"] if n == "bench.window"]
    ops = [e for p in planes if p["name"].startswith("/device:")
           for ln in p["lines"] if ln["name"] == "XLA Ops"
           for e in ln["events"]]
    clipped = [(n, max(s, lo), min(s + d, hi)) for n, s, d in ops
               if min(s + d, hi) > max(s, lo)]
    busy = covered([(a, b) for _, a, b in clipped]) / 1e9
    kernel = sum(b - a for n, a, b in clipped
                 if n.startswith("ragged_paged_attention")) / 1e9
    r = trace.reduce(planes)
    assert r.devices == 1
    assert r.window_s == pytest.approx((hi - lo) / 1e9)
    assert r.busy_s == pytest.approx(busy, rel=1e-12)
    assert 1 - r.busy_s / r.window_s == pytest.approx(1 - busy / 0.5)
    got = sum(s for n, s in r.op_s.items() if "ragged_paged_attention" in n)
    assert got == pytest.approx(kernel, rel=1e-12)
    assert r.collective_s == 0.0
    # self times add up to the busy time: nothing counted twice
    assert sum(r.op_s.values()) == pytest.approx(r.busy_s, rel=1e-9)
    # the idle gaps are the window less the busy time, longest first
    gaps = [s for _, s in r.idle_gaps]
    assert gaps == sorted(gaps, reverse=True)
    assert sum(gaps) <= r.window_s - r.busy_s + 1e-9


def test_made_up_trace_with_nesting_overlap_and_a_collective():
    ms = 1_000_000
    planes = [
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ["bench.window", 0, 100 * ms], ["bench.node_step", 0, 55 * ms],
            ["bench.step_wait", 55 * ms, 45 * ms]]}]},
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            ["while.1", 10 * ms, 40 * ms],
            ["ragged_paged_attention.7", 15 * ms, 20 * ms],
            ["all-reduce.3", 40 * ms, 5 * ms],
            ["fusion.2", 70 * ms, 10 * ms]]}]},
        {"name": "/device:TPU:1", "lines": [{"name": "XLA Ops", "events": [
            ["while.1", 10 * ms, 50 * ms],
            ["all-reduce.3", 55 * ms, 10 * ms]]}]},
    ]
    r = trace.reduce(planes)
    assert r.devices == 2 and r.window_s == pytest.approx(0.1)
    assert r.busy_s == pytest.approx((0.050 + 0.055) / 2)
    assert r.collective_s == pytest.approx((0.005 + 0.010) / 2)
    assert r.op_s["ragged_paged_attention.7"] == pytest.approx(0.020 / 2)
    assert r.op_s["while.1"] == pytest.approx((0.015 + 0.045) / 2)
    # device 0 idles 0-10 ms (in node_step), 50-70 ms (5 ms in node_step,
    # 15 in step_wait) and 80-100 ms (step_wait)
    assert r.idle_gaps == [["bench.step_wait", pytest.approx(0.020)],
                           ["bench.step_wait", pytest.approx(0.020)],
                           ["bench.node_step", pytest.approx(0.010)]]


class _Ctx:
    def __init__(self, r):
        self.trace = r


def test_trace_readers_on_the_chip_trace():
    r = trace.reduce(chip_trace())
    share = readers.attn_share(_Ctx(r))
    idle = readers.device_idle(_Ctx(r))
    assert 0 < share < 100 and 0 < idle < 100
    assert readers.attn_share(_Ctx(None)) is None
    assert trace.reduce([{"name": "/host:CPU", "lines": []}]) is None
