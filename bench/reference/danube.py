"""Plain reference of the h2o-danube block, in float32 ``jax.numpy``.

Pre-norm decoder (arXiv:2401.16818, a Llama/Mistral block): RMSNorm, rotary
position embedding (rotate-half form), grouped-query attention with an
optional sliding window, the SwiGLU MLP, a final RMSNorm and the output head.
No cache, no kernel, no batching of requests into one row: every sequence is
its own row, attended causally from position 0.

It reads the benchmark's weights in the layout of the configuration's
model module (``bench/models/dense_gqa.py``, ``weight_shapes``: layers
stacked on the leading axis, norm scales zero-centred so a norm multiplies
by 1 + scale) and upcasts them to float32 one layer at a time, inside the
layer's computation, so float32 copies of the whole model never exist.
Every product runs at ``precision="highest"``.  Queries are taken in blocks,
so the score matrix of a block is the largest buffer.

``quant="fp8"`` is the control: every matrix product's operands rounded to
float8 e4m3 (activations per row, weights per output channel, each scaled to
the format's largest value, 448), the rest as above.

Imports nothing of the program under test.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 256          # queries per attention block
ROWS = 4               # sequences per pass


def _dims(arch: dict) -> tuple:
    d, h = arch["hidden_size"], arch["num_attention_heads"]
    return (d, h, arch["num_key_value_heads"], arch.get("head_dim") or d // h,
            arch.get("sliding_window"), float(arch["rope_theta"]),
            float(arch["rms_norm_eps"]))


def fp8_round(x, axes):
    """``x`` rounded to float8 e4m3 (3 mantissa bits, smallest normal
    exponent -6, largest value 448) after scaling its largest magnitude
    over ``axes`` to 448; returned unscaled, in float32."""
    s = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    a = jnp.abs(x) / s
    e = jnp.maximum(jnp.floor(jnp.log2(jnp.maximum(a, 1e-30))), -6.0)
    step = jnp.exp2(e - 3.0)
    q = jnp.minimum(jnp.round(a / step) * step, 448.0)
    return jnp.sign(x) * q * s


def _mm(spec: str, a, w, quant, w_axes):
    """``einsum(spec, a, w)`` in float32 at the highest precision; under the
    control, operands rounded to fp8 first (``a`` per row over its
    contracted trailing axes, ``w`` per output channel over ``w_axes``)."""
    if quant == "fp8":
        n_contract = len(w_axes)
        a = fp8_round(a, tuple(range(a.ndim - n_contract, a.ndim)))
        w = fp8_round(w, w_axes)
    return jnp.einsum(spec, a, w, precision=HI)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + scale)


def _rope(x, pos, theta):
    """x (B, L, heads, D) rotated at positions pos (B, L); rotate-half."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[..., None, None].astype(jnp.float32) * freq
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("dims", "quant"))
def _layer(x, seg, i, pos, valid, *, dims, quant):
    """Layer ``i`` of the stacked weights ``seg`` on x (B, L, d) float32."""
    d, H, K, D, window, theta, eps = dims
    lp = jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False)
        .astype(jnp.float32), seg)
    B, L, _ = x.shape
    h = _rmsnorm(x, lp["norm_attn"]["scale"], eps)
    at = lp["attn"]
    q = _rope(_mm("bld,dhk->blhk", h, at["wq"], quant, (0,)), pos, theta)
    k = _rope(_mm("bld,dhk->blhk", h, at["wk"], quant, (0,)), pos, theta)
    v = _mm("bld,dhk->blhk", h, at["wv"], quant, (0,))
    G = H // K
    # query head j reads kv head j // G
    k = jnp.repeat(k, G, axis=2)
    v = jnp.repeat(v, G, axis=2)
    outs = []
    for q0 in range(0, L, Q_BLOCK):
        qb = q[:, q0:q0 + Q_BLOCK]
        qp = pos[:, q0:q0 + Q_BLOCK]
        s = jnp.einsum("bqhk,bshk->bhqs", qb, k, precision=HI) * D ** -0.5
        mask = (pos[:, None, :] <= qp[:, :, None]) & valid[:, None, :]
        if window is not None:
            mask &= (qp[:, :, None] - pos[:, None, :]) < window
        s = jnp.where(mask[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        p = jnp.where(mask[:, None], p, 0.0)
        outs.append(jnp.einsum("bhqs,bshk->bqhk", p, v, precision=HI))
    o = jnp.concatenate(outs, axis=1)
    x = x + _mm("blhk,hkd->bld", o, at["wo"], quant, (0, 1))
    h = _rmsnorm(x, lp["norm_mlp"]["scale"], eps)
    m = lp["mlp"]
    g = _mm("bld,df->blf", h, m["w_gate"], quant, (0,))
    u = _mm("bld,df->blf", h, m["w_up"], quant, (0,))
    return x + _mm("blf,fd->bld", jax.nn.silu(g) * u, m["w_down"], quant,
                   (0,))


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, norm, table, *, eps, quant):
    h = _rmsnorm(x, norm.astype(jnp.float32), eps)
    return _mm("nd,vd->nv", h, table.astype(jnp.float32), quant, (1,))


def logits(params, arch: dict, seqs, want, *, quant: str | None = None):
    """Per sequence ``seqs[b]`` (token ids), the logits (len(want[b]), V)
    predicting the token after each position in ``want[b]``; float32 numpy.

    ``quant`` None is the reference; ``"fp8"`` the control."""
    dims = _dims(arch)
    eps = dims[-1]
    seg = params["segments"][0][0]
    table = params["head" if "head" in params else "embed"]["table"]
    n_layers = arch["num_hidden_layers"]
    out = []
    for b0 in range(0, len(seqs), ROWS):
        rows, rows_want = seqs[b0:b0 + ROWS], want[b0:b0 + ROWS]
        L = -(-max(len(s) for s in rows) // Q_BLOCK) * Q_BLOCK
        toks = np.zeros((len(rows), L), np.int32)
        valid = np.zeros((len(rows), L), bool)
        for r, s in enumerate(rows):
            toks[r, :len(s)] = s
            valid[r, :len(s)] = True
        pos = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), toks.shape)
        x = params["embed"]["table"][jnp.asarray(toks)].astype(jnp.float32)
        valid = jnp.asarray(valid)
        for i in range(n_layers):
            x = _layer(x, seg, i, pos, valid, dims=dims, quant=quant)
        bi = np.concatenate([np.full(len(w), r) for r, w in
                             enumerate(rows_want)]).astype(np.int32)
        pi = np.concatenate(rows_want).astype(np.int32)
        lg = np.asarray(_head(x[bi, pi], params["final_norm"]["scale"], table,
                              eps=eps, quant=quant))
        splits = np.cumsum([len(w) for w in rows_want])[:-1]
        out += np.split(lg, splits)
        del x
    return out
