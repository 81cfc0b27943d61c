"""One run of one cell: set-up, the measured window, the metrics, the check.

Set-up, all of it counted in ``setup_s``: the weights from the seed, the
deployment, one warm-up request that compiles the tick (or loads it from
the persistent cache), and the mix's warm phase.  Then the window, with no
compilation inside it.  At the close the drive stops: every number is read
from what the window produced, and a request still in flight then counts as
the close says (``drive.censored``).  Then, with the peak memory read, the
deployment is stopped and its state freed, and the plain reference checks a
sample of what the window served.
"""
from __future__ import annotations

import gc
import glob
import shutil
import sys
import tempfile
import threading
import time
from dataclasses import dataclass
from pathlib import Path

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from . import correct, spec, trace
from .drive import Drive, pct
from .model import check_layout, make_weights
from .peaks import peak
from .traffic import Mix

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/backend_compile_duration")


class CompileCounter:
    """Times at which JAX traced or compiled something, process-wide."""

    def __init__(self):
        self.times: list[float] = []
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event in _COMPILE_EVENTS:
            with self._lock:
                self.times.append(time.monotonic())

    def between(self, a: float, b: float) -> int:
        with self._lock:
            return sum(a <= t < b for t in self.times)


class GcPauses:
    """Python's garbage collections while it is open: when each began, how
    long it held the interpreter, and its generation."""

    def __init__(self):
        self.pauses: list[tuple[float, float, int]] = []
        self._t = None
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.monotonic()
        elif self._t is not None:
            self.pauses.append((self._t, time.monotonic() - self._t,
                                info["generation"]))
            self._t = None

    def close(self) -> None:
        gc.callbacks.remove(self._on)

    def between(self, a: float, b: float) -> list[tuple[float, float, int]]:
        return [p for p in self.pauses if a <= p[0] < b]


def use_compile_cache(path: Path) -> None:
    """JAX's persistent compilation cache at a fixed path, every program in
    it however fast it compiled, so that only a checkout's first run of a
    cell compiles."""
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclass
class Context:
    """What a metric reader reads."""
    cell: spec.Cell
    arch: dict
    drive: Drive
    setup_s: float
    window_s: float
    token_budget: int
    chips: int
    device_kind: str
    trace: trace.Reduced | None

    def delta(self, key: str) -> int:
        return self.drive.delta(key)

    def peak(self) -> dict:
        return peak(self.device_kind)

    def due(self):
        return self.drive.in_window()

    def close_s(self) -> float:
        return self.drive.close_s


def device_info() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_bytes(devices) -> int | None:
    stats = [d.memory_stats() for d in devices]
    if any(s is None for s in stats):
        return None
    return max(s["peak_bytes_in_use"] for s in stats)


@dataclass
class Served:
    """What one run's set-up and window left: the drive with its records
    and counters, the set-up split, and the device facts."""
    drive: Drive
    setup_s: float
    split: dict
    mem_peak: int | None
    devices: list
    token_budget: int
    trace_dir: str | None
    compiles_in_window: int
    gc_in_window: list


def serve(cell: spec.Cell, params, seed: int, seconds: float, traced: bool,
          *, t_proc0: float, compiles: CompileCounter) -> Served:
    """Deploy, compile, run the warm phase and the window, read the peak
    memory, and free the deployment."""
    from repro.serving.cluster import ServeNode

    arch = cell.config
    dep_cfg = arch["deployment"]
    vocab = cell.model.dims(arch)["V"]
    t_ready = time.monotonic()
    node = ServeNode(n_workers=1)
    kw = {k: dep_cfg[k] for k in ("num_blocks", "kv_dtype",
                                  "devices_per_replica")
          if dep_cfg.get(k) is not None}
    dep = node.deploy(cell.name,
                      cell.model.program_config(cell.config_name, arch),
                      params, n_replicas=1, n_slots=dep_cfg["n_slots"],
                      max_len=dep_cfg["max_len"], **kw)
    engine = dep.engines[0]
    devices = (list(engine.mesh.devices.flat) if engine.mesh is not None
               else [jax.devices()[0]])
    t_deploy = time.monotonic()

    # one request through the whole path compiles the tick before any
    # traffic, so that no run's warm phase or window waits on a compile
    warm = np.random.default_rng(np.random.SeedSequence([int(seed) % 2**128,
                                                         3]))
    dep.submit("warmup", "warmup",
               warm.integers(0, vocab, 48, dtype=np.int32),
               max_new_tokens=2)
    node.run_until_drained(timeout_s=1200)
    t_compiled = time.monotonic()

    mix = Mix(cell.traffic, seed, vocab=vocab,
              max_len=dep_cfg["max_len"])
    drive = Drive(node, dep, mix, warm_s=cell.traffic["warm_s"],
                  window_s=seconds)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
    tracing = False
    gcs = GcPauses()
    drive.start()
    try:
        if traced:
            time.sleep(max(0.0, drive.t_open - 1.0 - time.monotonic()))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            tracing = True
        drive.opened.wait()
        with TraceAnnotation("bench.window"):
            drive.closed.wait()
        if tracing:
            jax.profiler.stop_trace()
            tracing = False
    finally:
        drive.stop()
        gcs.close()
        if tracing:
            jax.profiler.stop_trace()
    served = Served(
        drive=drive, setup_s=drive.open_s - t_proc0,
        split={"deploy_s": t_deploy - t_ready,
               "compile_s": t_compiled - t_deploy,
               "warm_s": drive.open_s - t_compiled},
        mem_peak=peak_bytes(devices), devices=devices,
        token_budget=engine.token_budget, trace_dir=trace_dir,
        compiles_in_window=compiles.between(drive.open_s, drive.close_s),
        gc_in_window=gcs.between(drive.open_s, drive.close_s))
    # the program's state goes before the reference runs; the weights are
    # the benchmark's own and stay
    node.undeploy(cell.name)
    node.close()
    drive.node = drive.dep = None
    del node, dep, engine
    gc.collect()
    return served


def context(cell: spec.Cell, served: Served, device_kind: str) -> Context:
    """What the readers read of one run's window (no trace yet)."""
    d = served.drive
    return Context(cell=cell, arch=cell.config, drive=d,
                   setup_s=served.setup_s, window_s=d.close_s - d.open_s,
                   token_budget=served.token_budget,
                   chips=len(served.devices), device_kind=device_kind,
                   trace=None)


def report(served: Served) -> None:
    """The earlier lines: set-up split, sample counts, lateness, the
    longest step and garbage collections inside the window (a stall of the
    whole process shows in all three), compilations inside the window, peak
    memory."""
    d = served.drive
    due, done = d.in_window(), d.finished_in_window()
    late = [r.sent - r.due for r in due if r.sent is not None]
    log("setup split: " + ", ".join(f"{k} {v:.3f}"
                                    for k, v in served.split.items()))
    log(f"window {d.close_s - d.open_s:.3f} s: {len(due)} requests due, "
        f"{len(done)} finished, ticks {d.delta('ticks')}, tokens out "
        f"{d.delta('tokens_out')}, prefill tokens "
        f"{d.delta('prefill_tokens')}, prefix-hit tokens "
        f"{d.delta('prefix_hit_tokens')}")
    if late:
        log(f"generator lateness: max {max(late) * 1e3:.3f} ms, p95 "
            f"{pct(late, 95) * 1e3:.3f} ms over {len(late)} sends")
    t, dt = d.longest_step
    log(f"longest node.step inside the window: {dt * 1e3:.3f} ms at "
        f"+{(t or d.open_s) - d.open_s:.3f} s")
    pauses = served.gc_in_window
    if pauses:
        t, dt, _ = max(pauses, key=lambda p: p[1])
        log(f"gc inside the window: {len(pauses)} collections "
            f"({sum(p[2] == 2 for p in pauses)} of generation 2), "
            f"{sum(p[1] for p in pauses) * 1e3:.3f} ms in all, longest "
            f"{dt * 1e3:.3f} ms at +{t - d.open_s:.3f} s")
    log(f"compilations inside the window: {served.compiles_in_window}")
    log(f"peak_bytes_in_use: {served.mem_peak}")


def build(cell: spec.Cell, seed: int):
    """The seed's weights in the layout of the cell's model module, made on
    the device, checked against the program's layout."""
    arch = cell.config
    params = make_weights(cell.model.weight_shapes(arch), seed)
    jax.block_until_ready(params)
    check_layout(params, cell.model.program_config(cell.config_name, arch))
    return params


def run_cell(cell: spec.Cell, seed: int, seconds: float, traced: bool, *,
             t_proc0: float, cache_dir: Path | None = None) -> dict:
    """Run ``cell`` once and return its result line (a dict)."""
    if cache_dir is not None:
        use_compile_cache(cache_dir)
    compiles = CompileCounter()
    info = device_info()
    arch = cell.config
    t0 = time.monotonic()
    params = build(cell, seed)
    t_weights = time.monotonic()
    served = serve(cell, params, seed, seconds, traced, t_proc0=t_proc0,
                   compiles=compiles)
    served.split = {"imports_s": t0 - t_proc0, "weights_s": t_weights - t0,
                    **served.split}
    report(served)
    drive = served.drive
    due = drive.in_window()
    # requests due in the window that the program answered with an error
    # before the close (the program dates every answer, errors too)
    failed = sum(1 for r in due if r.req is not None
                 and r.req.error is not None
                 and r.req.done_s < drive.close_s)

    sample = correct.sample(drive.finished_in_window(), seed)
    t_ref = time.monotonic()
    # nothing finished in the window, nothing compared: no number, and not
    # correct
    gap = (correct.widest_gap(spec.reference(cell), params, arch, sample)
           if sample else None)
    t_ref = time.monotonic() - t_ref
    limit = arch["correct"]["max_logit_gap"]
    checks = {"max_logit_gap": {"value": gap, "limit": limit},
              "failed_requests": {"value": failed, "limit": 0}}
    log(f"compared {sum(len(r.req.tokens) for r in sample)} served tokens "
        f"of {len(sample)} requests with the reference in {t_ref:.3f} s")

    ctx = context(cell, served, info["kind"])
    breakdown = None
    if traced:
        files = glob.glob(f"{served.trace_dir}/plugins/profile/*/*.xplane.pb")
        reduced = trace.reduce(trace.load(files[0])) if files else None
        shutil.rmtree(served.trace_dir, ignore_errors=True)
        ctx.trace = reduced
        if reduced is not None:
            info.update(busy_s=reduced.busy_s, window_s=reduced.window_s)
            breakdown = {"device_ops": reduced.top_ops,
                         "idle_gaps": reduced.idle_gaps}
    metrics = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        value = spec.metric_reader(cell, m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    info["memory_peak_bytes"] = served.mem_peak
    ok = gap is not None and gap <= limit and failed == 0
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    out = {"correct": bool(ok), "attempted": len(due), "failed": failed,
           "metrics": metrics, "device": info}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out
