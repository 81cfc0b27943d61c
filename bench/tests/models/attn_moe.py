"""A model module of another family, kept with the tests: the grouped-query
decoder with a top-k mixture of experts and shared experts in place of the
MLP (the program's ``family="moe"``, every layer ``attn_moe``).

Configuration keys follow the Mixtral and DeepSeek-MoE configs:
``num_local_experts`` (the experts held), ``num_experts_per_tok``,
``n_shared_experts`` and ``moe_intermediate_size`` (each expert's width).
The interface is set out in ``bench/harness/model.py``.
"""
from __future__ import annotations

import jax.numpy as jnp


def dims(arch: dict) -> dict:
    d, h = arch["hidden_size"], arch["num_attention_heads"]
    return {"d": d, "H": h, "K": arch["num_key_value_heads"],
            "D": arch.get("head_dim") or d // h,
            "E": arch["num_local_experts"], "k": arch["num_experts_per_tok"],
            "S": arch["n_shared_experts"], "F": arch["moe_intermediate_size"],
            "L": arch["num_hidden_layers"], "V": arch["vocab_size"],
            "dtype": arch["torch_dtype"]}


def program_config(name: str, arch: dict):
    from repro.models.config import ModelConfig

    m = dims(arch)
    return ModelConfig(
        name=name, family="moe", n_layers=m["L"], d_model=m["d"],
        n_heads=m["H"], n_kv_heads=m["K"], head_dim=m["D"],
        d_ff=arch["intermediate_size"], vocab_size=m["V"],
        tie_embeddings=bool(arch["tie_word_embeddings"]),
        window=arch.get("sliding_window"),
        rope_theta=float(arch["rope_theta"]), dtype=m["dtype"],
        n_experts=m["E"], top_k=m["k"], n_shared_experts=m["S"],
        moe_d_ff=m["F"],
        # room for every token in every expert: none is dropped, as the
        # reference drops none
        capacity_factor=m["E"] / m["k"],
        max_target_length=arch.get("max_position_embeddings", 4096))


def weight_shapes(arch: dict) -> dict:
    m = dims(arch)
    d, H, K, D, E, S, F, L, V = (m[k] for k in "dHKDESFLV")
    dt = jnp.dtype(m["dtype"])
    f32 = jnp.float32
    return {
        "embed": {"table": ((V, d), dt, 1)},
        "final_norm": {"scale": ((d,), f32, None)},
        "segments": (({
            "norm_attn": {"scale": ((L, d), f32, None)},
            "attn": {"wq": ((L, d, H, D), dt, d), "wk": ((L, d, K, D), dt, d),
                     "wv": ((L, d, K, D), dt, d),
                     "wo": ((L, H, D, d), dt, H * D)},
            "norm_mlp": {"scale": ((L, d), f32, None)},
            "moe": {"router": ((L, d, E), f32, d),
                    "w_gate": ((L, E, d, F), dt, d),
                    "w_up": ((L, E, d, F), dt, d),
                    "w_down": ((L, E, F, d), dt, F),
                    "shared": {"w_gate": ((L, d, S * F), dt, d),
                               "w_up": ((L, d, S * F), dt, d),
                               "w_down": ((L, S * F, d), dt, S * F)}},
        },),),
        "head": {"table": ((V, d), dt, d)},
    }


def matmul_flops(arch: dict, lanes: int, emitted: int) -> float:
    """Per lane and layer: q, k, v, o, the router, and the gated MLPs of
    the ``k`` experts the token is routed to and of the shared experts;
    per emitted token the head."""
    m = dims(arch)
    d, H, K, D = m["d"], m["H"], m["K"], m["D"]
    per_layer = (d * (H + 2 * K) * D + H * D * d + d * m["E"]
                 + (m["k"] + m["S"]) * 3 * d * m["F"])
    return 2.0 * m["L"] * per_layer * lanes + 2.0 * d * m["V"] * emitted
