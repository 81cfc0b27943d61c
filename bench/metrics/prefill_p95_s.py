"""prefill_p95_s: p95, over every request due in the window, of the time
from its issue (a slot granted) to its first token, the chunked prefill;
each time is censored at the window's close as in ``queue_wait_p95_s``."""
from bench.harness.drive import censored, pct


def read(ctx):
    close = ctx.close_s()
    xs = [censored(r.req.first_token_s if r.req is not None else None, close)
          - censored(r.req.issued_s if r.req is not None else None, close)
          for r in ctx.due()]
    return pct(xs, 95) if xs else None
