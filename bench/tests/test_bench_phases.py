"""The program's phases in a trace (``harness.phases``): device scopes from
the tick's HLO, host spans and their arguments, idle gaps put down to the
innermost span; on a made-up trace counted by hand, on a small trace
recorded on the chip with ``bench/phases.py --record``, and on a traced
tiny run on the CPU.  Also: the first checked-in chip trace reads exactly
as it did, and the ``prefill_p95_s`` reader."""
import glob
import gzip
import json
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench.harness import cell_run, phases, readers, spec, trace
from bench.tests.tiny import make_root

DATA = Path(__file__).resolve().parent / "data"
ROOT = Path(__file__).resolve().parents[2]
MS = 1_000_000


def _data(name):
    return json.loads(gzip.decompress((DATA / name).read_bytes()))


def test_the_first_chip_trace_reads_as_it_did():
    """trace_v5e_chat.json.gz: the numbers the trace readers gave on it
    when it was recorded, to the last digit, and the same base numbers
    from ``phases.reduce``."""
    planes = _data("trace_v5e_chat.json.gz")["planes"]
    r = trace.reduce(planes)
    assert (r.window_s, r.busy_s, r.devices, r.collective_s) == (
        0.5, 0.486667641, 1, 0.0)
    assert len(r.op_s) == 91
    assert r.top_ops[0] == ["ragged_paged_attention.7", 0.343433992]
    assert r.idle_gaps[0] == ["bench.node_step", 0.005550926]
    ctx = SimpleNamespace(trace=r)
    assert readers.attn_share(ctx) == 70.56848721117252
    assert readers.device_idle(ctx) == 2.666471800000003
    ph = phases.reduce({"planes": planes, "module": None, "op_names": {}})
    assert ph.base == r
    assert set(ph.scope_s) == {phases.OTHER}


HLO = """HloModule jit__mixed, entry_computation_layout={()->()}

%body {
  %copy.5 = f32[8]{0} copy(%p), metadata={op_name="jit(_mixed)/jit(main)/while/body/closed_call/jit(ragged_paged_attention)/kv_pool/layout/transpose" stack_frame_id=3}
  ROOT %ragged_paged_attention.7 = f32[8]{0} custom-call(%copy.5), custom_call_target="tpu_custom_call", metadata={op_name="jit(_mixed)/while/body/closed_call/jit(ragged_paged_attention)/attention/ragged_paged_attention/pallas_call"}
  %fusion.2 = f32[8]{0} fusion(%x), kind=kLoop, metadata={op_name="jit(_mixed)/while/body/closed_call/qkv/btd,dhk->bthk/dot_general"}
  %copy.9 = f32[8]{0} copy(%y), metadata={op_name="jit(_mixed)/while/body/dynamic_slice"}
}
"""


def test_op_names_and_scopes_from_hlo_text():
    module, names = phases.op_names(HLO)
    assert module == "jit__mixed"
    assert {n: phases.scope_of(o) for n, o in names.items()} == {
        "copy.5": "kv_pool/layout",
        "ragged_paged_attention.7": "attention/ragged_paged_attention",
        "fusion.2": "qkv", "copy.9": ""}


def _made_up():
    """One tick in a 100 ms window.  Host: node_step 0-60 holds pack 2-10,
    dispatch 10-12 (with counts), sync 12-40, emit 40-55 (a completion
    45-50 inside it); step_wait 60-100; an upcall 70-75 on another thread.
    Device: the tick module 12-38 with a while 12-38 holding the kernel
    14-30 and a layout copy 30-36; another program's op 80-85."""
    host = [["bench.window", 0, 100 * MS], ["bench.node_step", 0, 60 * MS],
            ["cascade.tick.pack", 2 * MS, 8 * MS],
            ["cascade.tick.dispatch", 10 * MS, 2 * MS],
            ["cascade.tick.sync", 12 * MS, 28 * MS],
            ["cascade.tick.emit", 40 * MS, 15 * MS],
            ["cascade.complete", 45 * MS, 5 * MS],
            ["bench.step_wait", 60 * MS, 40 * MS]]
    args = [None] * len(host)
    args[3] = {"attn_grid_blocks": 32 * 128, "attn_live_blocks": 1000,
               "attn_pad_blocks": 40}
    return {"module": "jit__mixed", "op_names": phases.op_names(HLO)[1],
            "planes": [
                {"name": "/host:CPU", "lines": [
                    {"name": "python3", "events": host, "args": args},
                    {"name": "upcall-0", "events": [
                        ["cascade.upcall", 70 * MS, 5 * MS]]}]},
                {"name": "/device:TPU:0", "lines": [
                    {"name": "XLA Modules", "events": [
                        ["jit__mixed(123)", 12 * MS, 26 * MS],
                        ["jit_other(9)", 80 * MS, 5 * MS]]},
                    {"name": "XLA Ops", "events": [
                        ["while.1", 12 * MS, 26 * MS],
                        ["ragged_paged_attention.7", 14 * MS, 16 * MS],
                        ["copy.5", 30 * MS, 6 * MS],
                        ["fusion.2", 80 * MS, 5 * MS]]}]}]}


def test_made_up_trace_by_hand():
    rec = _made_up()
    ph = phases.reduce(rec)
    assert ph.base == trace.reduce(rec["planes"])
    assert ph.base.busy_s == pytest.approx(0.031)
    s = ph.scope_s
    assert s["attention/ragged_paged_attention"] == pytest.approx(0.016)
    assert s["kv_pool/layout"] == pytest.approx(0.006)
    # the while's own time (12-14, 36-38) is the tick's and unnamed;
    # fusion.2 ran in another program, whatever its name in the tick
    assert s[phases.UNSCOPED] == pytest.approx(0.004)
    assert s[phases.OTHER] == pytest.approx(0.005)
    assert ph.mapped == pytest.approx(22 / 26)
    assert ph.share("kv_pool") == pytest.approx(0.006 / 0.031)
    assert ph.ticks() == 1
    assert ph.span_s["cascade.tick.sync"] == pytest.approx(0.028)
    assert ph.span_n == {k: 1 for k in (
        "cascade.tick.pack", "cascade.tick.dispatch", "cascade.tick.sync",
        "cascade.tick.emit", "cascade.complete", "cascade.upcall")}
    assert ph.counts == {"attn_grid_blocks": 4096, "attn_live_blocks": 1000,
                         "attn_pad_blocks": 40}
    # idle 0-12: node_step 0-2, pack 2-10, dispatch 10-12; 38-80: sync
    # 38-40, emit 40-45 and 50-55, complete 45-50, node_step 55-60,
    # step_wait 60-70 and 75-80, the upcall 70-75 (depth 0, started
    # later than step_wait); 85-100: step_wait
    assert ph.idle_s == pytest.approx({
        "bench.node_step": 0.007, "cascade.tick.pack": 0.008,
        "cascade.tick.dispatch": 0.002, "cascade.tick.sync": 0.002,
        "cascade.tick.emit": 0.010, "cascade.complete": 0.005,
        "bench.step_wait": 0.030, "cascade.upcall": 0.005})
    assert ph.idle_gaps == [["bench.step_wait", pytest.approx(0.042)],
                            ["bench.step_wait", pytest.approx(0.015)],
                            ["cascade.tick.pack", pytest.approx(0.012)]]


def test_the_recorded_chip_trace():
    """trace_v5e_chat_phases.json.gz: danube18b.chat on one TPU v5 lite,
    recorded by bench/phases.py --record, with the tick's op_names."""
    rec = _data("trace_v5e_chat_phases.json.gz")
    ph = phases.reduce(rec)
    assert ph.base == trace.reduce(rec["planes"])
    assert 0 < ph.base.busy_s <= ph.base.window_s
    # every op of the tick is named by the tick's HLO, and the kernel is
    # found as before
    assert ph.mapped == 1.0
    assert readers.attn_share(SimpleNamespace(trace=ph.base)) > 50
    assert ph.scope_s["attention/ragged_paged_attention"] == pytest.approx(
        sum(t for n, t in ph.base.op_s.items()
            if n.startswith(readers.ATTN_KERNEL)))
    assert 0 < ph.share("kv_pool") < 0.5
    assert sum(ph.scope_s.values()) == pytest.approx(ph.base.busy_s)
    # the tick's phases, once per tick, and the counts they carry
    ticks = ph.ticks()
    assert ticks >= 1
    for name in ("cascade.tick.pack", "cascade.tick.sync",
                 "cascade.tick.emit"):
        assert abs(ph.span_n[name] - ticks) <= 1
    c = ph.counts
    assert 0 <= c["attn_pad_blocks"] <= c["attn_live_blocks"] \
        <= c["attn_grid_blocks"] == ticks * 32 * 128
    # the idle time is put down to the program's phases
    assert sum(ph.idle_s.values()) == pytest.approx(
        ph.base.window_s - ph.base.busy_s, rel=1e-6)
    assert ph.idle_gaps[0][0].startswith("cascade.")


def _rec(due, issued=None, first=None):
    req = None if issued is None else SimpleNamespace(
        issued_s=issued, first_token_s=first)
    return SimpleNamespace(due=due, req=req)


def test_prefill_reader():
    read = spec.metric_reader(spec.load_cell(ROOT, "danube18b.chat"),
                              "prefill_p95_s")
    recs = [_rec(0.0, 1.0, 3.0), _rec(0.5, 2.0, None), _rec(1.0, 12.0, 15.0),
            _rec(2.0)]
    ctx = SimpleNamespace(due=lambda: recs, close_s=lambda: 10.0)
    # prefill spans 2, 8 (no first token by the close), 0 (issued after
    # the close), 0 (in flight): p95 of [2, 8, 0, 0]
    assert read(ctx) == pytest.approx(readers.pct([2.0, 8.0, 0.0, 0.0], 95))
    ctx = SimpleNamespace(due=lambda: [], close_s=lambda: 10.0)
    assert read(ctx) is None


def test_a_traced_tiny_run_nests_the_tick_in_the_step(tmp_path):
    """On the CPU: each cascade.tick.* span of the stepping thread lies in
    one bench.node_step, one of each per step that dispatched (the window's
    ticks, and those of the traced second before it)."""
    cell = spec.load_cell(make_root(tmp_path), "tiny.chat")
    params = cell_run.build(cell, 11)
    served = cell_run.serve(cell, params, 11, 1.0, True,
                            t_proc0=time.monotonic(),
                            compiles=cell_run.CompileCounter())
    path = glob.glob(f"{served.trace_dir}/plugins/profile/*/*.xplane.pb")[0]
    rec = phases.load(path)
    tick = ("cascade.tick.pack", "cascade.tick.dispatch",
            "cascade.tick.sync", "cascade.tick.emit")
    seen = 0
    for p in rec["planes"]:
        for ln in p["lines"]:
            steps = [(s, s + d) for n, s, d in ln["events"]
                     if n == "bench.node_step"]
            # a step the trace's start or stop cut is not in it
            inner = [(n, s, s + d) for n, s, d in ln["events"] if n in tick
                     and steps and steps[0][0] <= s <= steps[-1][1]]
            if not inner:
                continue
            per_step = {}
            for n, a, b in inner:
                (k,) = [i for i, (s, e) in enumerate(steps)
                        if s <= a and b <= e]
                per_step.setdefault(k, []).append(n)
            for names in per_step.values():
                assert names == list(tick)
            seen += len(per_step)
    assert seen >= served.drive.delta("ticks") > 0
