"""The plain reference computes what the program's own float32 forward
computes, at the registry's SMOKE sizes (and, for the expert layer kept
with the tests, at the tiny attn_moe size), and the fp8 control does not."""

import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness.model import make_weights
from bench.harness.spec import _load_module
from bench.models import dense_gqa
from bench.reference import danube
from bench.tests.tiny import TESTS, TINY_MOE_ARCH
from repro.configs.registry import get_config
from repro.models import forward


def smoke_arch(arch_id: str) -> dict:
    c = get_config(arch_id, smoke=True)
    return {"hidden_size": c.d_model, "intermediate_size": c.d_ff,
            "num_attention_heads": c.n_heads,
            "num_key_value_heads": c.n_kv_heads, "head_dim": c.head_dim,
            "num_hidden_layers": c.n_layers, "vocab_size": c.vocab_size,
            "sliding_window": c.window, "rope_theta": c.rope_theta,
            # the program's norm uses 1e-6 whatever the config says
            "rms_norm_eps": 1e-6, "tie_word_embeddings": c.tie_embeddings,
            "torch_dtype": "float32", "max_position_embeddings": 64}


@pytest.mark.parametrize("arch_id", ["h2o-danube-1.8b", "h2o-danube-3-4b",
                                     "attn_moe"])
def test_reference_matches_the_programs_forward(arch_id):
    if arch_id == "attn_moe":
        arch = TINY_MOE_ARCH
        mod = _load_module(TESTS / "models" / "attn_moe.py")
        ref_mod = _load_module(TESTS / "reference" / "attn_moe.py")
    else:
        arch, mod, ref_mod = smoke_arch(arch_id), dense_gqa, danube
    cfg = mod.program_config(arch_id, arch).replace(attn_backend="xla")
    params = make_weights(mod.weight_shapes(arch), 3)
    rng = np.random.default_rng(0)
    seqs = [rng.integers(0, arch["vocab_size"], n, dtype=np.int32)
            for n in (40, 23)]
    want = [np.arange(len(s)) for s in seqs]
    ref = ref_mod.logits(params, arch, seqs, want)
    for s, r in zip(seqs, ref):
        toks = jnp.asarray(s)[None]
        pos = jnp.arange(len(s), dtype=jnp.int32)[None]
        prog, _ = forward(params, toks, pos, cfg, mode="score")
        np.testing.assert_allclose(np.asarray(prog[0]), r, atol=2e-4,
                                   rtol=2e-4)


def test_control_reads_wider_gaps_than_the_reference_itself():
    arch = smoke_arch("h2o-danube-1.8b")
    arch = dict(arch, hidden_size=128, intermediate_size=256, head_dim=32)
    params = make_weights(dense_gqa.weight_shapes(arch), 5)
    rng = np.random.default_rng(1)
    seqs = [rng.integers(0, arch["vocab_size"], 48, dtype=np.int32)]
    want = [np.arange(48)]
    ref = danube.logits(params, arch, seqs, want)[0]
    ctl = danube.logits(params, arch, seqs, want, quant="fp8")[0]
    gap = ref.max(-1) - ref[np.arange(48), ctl.argmax(-1)]
    assert gap.max() > 0.05
    assert np.abs(ctl - ref).max() > 10 * 1e-4


def test_fp8_rounding_keeps_three_mantissa_bits():
    x = jnp.asarray([[448.0, 1.0, 1.06, 1.07, 0.3, -2.5, 0.0]])
    q = np.asarray(danube.fp8_round(x, (1,)))
    assert q.tolist() == [[448.0, 1.0, 1.0, 1.125, 0.3125, -2.5, 0.0]]
