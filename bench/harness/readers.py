"""The arithmetic behind the metric readers in ``bench/metrics``.

Each reader file names one metric and calls one of these; a reader that
finds nothing to read returns None and the metric is left out of the line.
Shares are in percent.
"""
from __future__ import annotations

from .drive import censored, pct

# The ragged paged-attention kernel as the trace names its events: the
# Pallas kernel's function name, which the compiled custom call carries.
ATTN_KERNEL = "ragged_paged_attention"


def ttft_p95(ctx):
    close = ctx.close_s()
    xs = [censored(r.req.first_token_s if r.req is not None else None,
                   close) - r.due for r in ctx.due()]
    return pct(xs, 95) if xs else None


def tpot_p95(ctx):
    xs = [(r.req.done_s - r.req.first_token_s) / (len(r.req.tokens) - 1)
          for r in ctx.drive.finished_in_window() if len(r.req.tokens) > 1]
    return pct(xs, 95) if xs else None


def queue_wait_p95(ctx):
    close = ctx.close_s()
    xs = [censored(r.req.issued_s if r.req is not None else None, close)
          - r.due for r in ctx.due()]
    return pct(xs, 95) if xs else None


def tokens_per_s(ctx):
    return ctx.delta("tokens_out") / ctx.window_s


def lanes(ctx) -> int:
    """Packed lanes the window's ticks processed: every prompt token, and
    every emitted token but the first of each request (which comes out of
    the prefill's last lane)."""
    return (ctx.delta("prefill_tokens") + ctx.delta("tokens_out")
            - ctx.delta("prefills"))


def tick_ms(ctx):
    ticks = ctx.delta("ticks")
    return 1e3 * ctx.drive.busy_step_s / ticks if ticks else None


def lane_occupancy(ctx):
    ticks = ctx.delta("ticks")
    return 100.0 * lanes(ctx) / (ticks * ctx.token_budget) if ticks else None


def mfu(ctx):
    """The window's matrix-product operations, as the cell's model module
    counts them, over the chips' peak for the window."""
    flops = ctx.cell.model.matmul_flops(ctx.arch, lanes(ctx),
                                        ctx.delta("tokens_out"))
    peak = ctx.peak()["bf16_flops_per_s"]
    return 100.0 * flops / (ctx.window_s * ctx.chips * peak)


def attn_share(ctx):
    t = ctx.trace
    if t is None or t.busy_s <= 0:
        return None
    kernel = sum(s for name, s in t.op_s.items() if ATTN_KERNEL in name)
    return 100.0 * kernel / t.busy_s if kernel > 0 else None


def device_idle(ctx):
    t = ctx.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
