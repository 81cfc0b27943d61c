"""The serving program's host spans, on the profiler's clock.

A span is a ``jax.profiler.TraceAnnotation``: with no trace active it costs
about a microsecond; with one active it lands on the same clock as the
device's ops, so a trace can put each idle gap of the device down to the
host phase that held it.  Spans are per tick and per request, never per
token.  Device-side phases are ``jax.named_scope`` metadata on the tick's
ops instead (``models.lm.paged_mixed_step``), and the ragged kernel's
custom call is named ``ragged_paged_attention``.

Keyword arguments become the span's arguments in the trace; the dispatch
span carries the tick's attention-grid counts (``EngineStats.attn_*``).
"""
# ServeEngine._tick_mixed, in order: preemption and steps 0-4 (decode
# lanes, continuing chunks, admission, drafts) and the block table; the
# host->device copies, the jitted call and publish(); the one blocking
# pull; the emission loop, first tokens and slot releases
TICK_PACK = "cascade.tick.pack"
TICK_DISPATCH = "cascade.tick.dispatch"
TICK_SYNC = "cascade.tick.sync"
TICK_EMIT = "cascade.tick.emit"
# ModelDeployment._complete_request: listeners and the store puts (inside
# TICK_EMIT when an engine completes the request, on the tick thread)
COMPLETE = "cascade.complete"
# ServeNode.step: the watchdog and the down-sweep of each deployment
NODE_HEALTH = "cascade.node.health"
# and ``cascade.upcall``, one lambda call on an upcall thread, is named in
# core.dispatcher.UpcallThreadPool (core does not import serving)

__all__ = ["TICK_PACK", "TICK_DISPATCH", "TICK_SYNC", "TICK_EMIT",
           "COMPLETE", "NODE_HEALTH"]
