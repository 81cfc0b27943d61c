"""A configuration's weights and its parameter layout, made the same way
for every model.

What belongs to one architecture lives in its model module,
``<bench>/models/<model>.py``, which the configuration file names under
``"model"`` (beside ``"reference"``, its plain reference) and
``spec.load_cell`` loads as ``Cell.model``.  The module provides:

- ``dims(arch) -> dict``: the sizes the rest of the benchmark reads by
  short name; at least ``V``, the vocabulary the traffic draws its token
  ids from, and whatever the module's own functions read;
- ``program_config(name, arch)``: the program's ``ModelConfig``;
- ``weight_shapes(arch)``: a tree of ``(shape, dtype, fan_in)`` leaves in
  the program's parameter layout (``fan_in`` None: a norm scale);
- ``matmul_flops(arch, lanes, emitted) -> float``: the matrix-product
  operations of ``lanes`` packed tokens and ``emitted`` head rows, which
  ``mfu`` divides by the chip's peak.  An expert layer counts the experts
  a token is routed to and any shared expert, never every expert held.

Where a configuration holds one chip's share of a stated deployment (its
experts, or a slice of its vocabulary), the count held here is the value
under its own key, the key is listed under ``reduced`` in
``BENCHMARK.json``, and the file states the cut beside it under
``"cut": {key: {"published": ..., "deployment": ...}}``; ``spec``
refuses a ``reduced`` key the file does not state so.  ``matmul_flops``
then counts what this chip computes: of the experts a token is routed
to, the share held here.  The module is also where a configuration keeps
the operation and byte counts of its own kernels, for a roofline reader
to import.

Here: ``make_weights`` builds random weights from the seed, on the device,
in one jitted call, in the dtype they are served in; ``check_layout``
holds them to the program's own initializer.  The weights are the
benchmark's, not the program's: the plain reference reads the same arrays.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[0], tuple)


def make_weights(shapes, seed: int):
    """Random weights of the tree ``shapes`` (a model module's
    ``weight_shapes``) from ``seed``, made on the device in one jitted call.

    Matrices are N(0, 1/fan_in), so activations stay O(1) through every
    layer; norm scales are N(0, 0.1), so the norms' weights take part in
    every comparison.  The output head (the embedding table, when tied) is
    scaled to fan-in as well: logits then have a spread of about 1, where a
    wrong block, head or position moves the greedy token."""
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
