"""The dense grouped-query decoder (the Llama and Mistral block), as the
program takes it: h2o-danube-1.8b and h2o-danube-3-4b name this module.

A configuration file holds the model's published config (Hugging Face key
names).  ``program_config`` maps it onto the program's ``ModelConfig``
(``family="dense"``), ``weight_shapes`` gives the program's parameter
layout, and ``matmul_flops`` the operations ``mfu`` counts.  The interface
is set out in ``bench/harness/model.py``.
"""
from __future__ import annotations

import jax.numpy as jnp


def dims(arch: dict) -> dict:
    """The sizes every other part of the benchmark reads, by short name."""
    d, h = arch["hidden_size"], arch["num_attention_heads"]
    return {"d": d, "H": h, "K": arch["num_key_value_heads"],
            "D": arch.get("head_dim") or d // h,
            "F": arch["intermediate_size"], "L": arch["num_hidden_layers"],
            "V": arch["vocab_size"], "window": arch.get("sliding_window"),
            "theta": float(arch["rope_theta"]),
            "eps": float(arch["rms_norm_eps"]),
            "tied": bool(arch["tie_word_embeddings"]),
            "dtype": arch["torch_dtype"]}


def program_config(name: str, arch: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.models.config import ModelConfig

    m = dims(arch)
    return ModelConfig(
        name=name, family="dense", n_layers=m["L"], d_model=m["d"],
        n_heads=m["H"], n_kv_heads=m["K"], head_dim=m["D"], d_ff=m["F"],
        vocab_size=m["V"], tie_embeddings=m["tied"], window=m["window"],
        rope_theta=m["theta"], dtype=m["dtype"],
        max_target_length=arch.get("max_position_embeddings", 4096))


def weight_shapes(arch: dict) -> dict:
    """(shape, dtype, fan_in) of every leaf, in the program's layout: the
    layers stacked on a leading axis, attention weights as (d, heads, D),
    norm scales zero-centred (the norm multiplies by 1 + scale) in f32."""
    m = dims(arch)
    d, H, K, D, F, L, V = (m[k] for k in "dHKDFLV")
    dt = jnp.dtype(m["dtype"])
    f32 = jnp.float32
    tree = {
        # a tied table is the output head too: scaled as the head is
        "embed": {"table": ((V, d), dt, d if m["tied"] else 1)},
        "final_norm": {"scale": ((d,), f32, None)},
        "segments": (({
            "norm_attn": {"scale": ((L, d), f32, None)},
            "attn": {"wq": ((L, d, H, D), dt, d), "wk": ((L, d, K, D), dt, d),
                     "wv": ((L, d, K, D), dt, d),
                     "wo": ((L, H, D, d), dt, H * D)},
            "norm_mlp": {"scale": ((L, d), f32, None)},
            "mlp": {"w_gate": ((L, d, F), dt, d), "w_up": ((L, d, F), dt, d),
                    "w_down": ((L, F, d), dt, F)},
        },),),
    }
    if not m["tied"]:
        tree["head"] = {"table": ((V, d), dt, d)}
    return tree


def layer_matmul_params(arch: dict) -> int:
    """Matrix parameters of one layer: q, k, v, o and the gated MLP."""
    m = dims(arch)
    d, H, K, D, F = m["d"], m["H"], m["K"], m["D"], m["F"]
    return d * (H + 2 * K) * D + H * D * d + 3 * d * F


def matmul_flops(arch: dict, lanes: int, emitted: int) -> float:
    """FLOPs of ``lanes`` processed tokens and ``emitted`` head rows: per
    lane 2 x the non-embedding matrix parameters of every layer, per
    emitted token the output head, 2 * d * V.  The attention score and
    value products are left out: they grow with the context, and the
    tick's kernel time is read from the trace instead."""
    m = dims(arch)
    return (2.0 * m["L"] * layer_matmul_params(arch) * lanes
            + 2.0 * m["d"] * m["V"] * emitted)
