"""A configuration file's model, as the program takes it, and its weights.

The configuration files hold the model's published config (Hugging Face
key names).  ``program_config`` maps them onto the program's ``ModelConfig``;
``make_weights`` builds random weights from the seed, on the device, in one
jitted call, in the dtype they are served in and in the program's parameter
layout.  The weights are the benchmark's, not the program's: the plain
reference reads the same arrays.
"""
from __future__ import annotations

import jax
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


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[0], tuple)


def make_weights(arch: dict, seed: int):
    """Random weights from ``seed``, made on the device in one jitted call.

    Matrices are N(0, 1/fan_in), so activations stay O(1) through every
    layer; norm scales are N(0, 0.1), so the norms' weights take part in
    every comparison.  The output head (the embedding table, when tied) is
    scaled to fan-in as well: logits then have a spread of about 1, where a
    wrong block, head or position moves the greedy token."""
    shapes = weight_shapes(arch)
    leaves, treedef = jax.tree.flatten(shapes, is_leaf=_is_leaf)

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, (shape, dt, fan_in) in zip(keys, leaves):
            x = jax.random.normal(k, shape, jnp.float32)
            scale = 0.1 if fan_in is None else fan_in ** -0.5
            out.append((x * scale).astype(dt))
        return jax.tree.unflatten(treedef, out)

    seed = int(seed) % 2**64
    key = jax.random.fold_in(jax.random.key(seed % 2**32), seed >> 32)
    return build(key)


def check_layout(params, cfg) -> None:
    """Raise unless ``params`` has exactly the tree, shapes and dtypes the
    program's own initializer gives ``cfg`` (nothing is allocated)."""
    from repro.models import init_params

    want = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError("the benchmark's weights no longer match the "
                         "program's parameter layout")
