"""Operations of the model's matrix multiplications, from its sizes.

The model FLOP utilization counts, per packed lane (a prompt token or a
decode token the tick processed), 2 x the non-embedding matrix parameters
(q, k, v, o and the gated MLP of every layer), and per emitted token the
output head, 2 * d * V.  The attention score and value products are left
out: they grow with the context, and the tick's kernel time is read from
the trace instead."""
from __future__ import annotations

from .model import dims


def layer_matmul_params(arch: dict) -> int:
    m = dims(arch)
    d, H, K, D, F = m["d"], m["H"], m["K"], m["D"], m["F"]
    return d * (H + 2 * K) * D + H * D * d + 3 * d * F


def matmul_flops(arch: dict, lanes: int, emitted: int) -> float:
    """FLOPs of ``lanes`` processed tokens and ``emitted`` head rows."""
    m = dims(arch)
    return (2.0 * m["L"] * layer_matmul_params(arch) * lanes
            + 2.0 * m["d"] * m["V"] * emitted)
