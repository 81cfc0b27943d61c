"""A configuration's model module: the danube configurations count what
they counted before it was a module, and a configuration of another family
goes through the harness with nothing but its own files."""
import jax
import pytest

from bench.harness import cell_run, model, spec
from bench.tests.tiny import TINY_MOE_ARCH, make_root

from .test_bench_spec import ROOT


def _parents_flops(arch: dict, lanes: int, emitted: int) -> float:
    """The count ``mfu`` used before configurations named a model module:
    2 x (q, k, v, o and the gated MLP) of every layer per lane, and the
    head, 2 * d * V, per emitted token."""
    d, H, K = (arch["hidden_size"], arch["num_attention_heads"],
               arch["num_key_value_heads"])
    D, F = arch.get("head_dim") or d // H, arch["intermediate_size"]
    layer = d * (H + 2 * K) * D + H * D * d + 3 * d * F
    return (2.0 * arch["num_hidden_layers"] * layer * lanes
            + 2.0 * d * arch["vocab_size"] * emitted)


@pytest.mark.parametrize("cell_name,per_lane", [
    ("danube18b.chat", 3_334_471_680.0),
    ("danube34b.batch", 7_431_782_400.0)])
def test_the_dense_module_counts_as_the_parent_did(cell_name, per_lane):
    cell = spec.load_cell(ROOT, cell_name)
    arch = cell.config
    assert cell.model.__name__.endswith("dense_gqa")
    assert cell.model.matmul_flops(arch, 1, 0) == per_lane
    for lanes, emitted in ((1, 0), (0, 1), (4096, 977), (31, 16)):
        assert (cell.model.matmul_flops(arch, lanes, emitted)
                == _parents_flops(arch, lanes, emitted))


@pytest.mark.parametrize("cell_name", ["danube18b.chat", "danube34b.batch"])
def test_the_dense_weights_keep_the_programs_layout(cell_name):
    """At full size, shapes only: the tree the module describes is the
    program's own (nothing is allocated)."""
    cell = spec.load_cell(ROOT, cell_name)
    shapes = cell.model.weight_shapes(cell.config)
    params = jax.eval_shape(lambda: model.make_weights(shapes, 2**33 + 1))
    model.check_layout(params, cell.model.program_config(cell.config_name,
                                                         cell.config))


def test_an_expert_configuration_builds_and_counts_the_routed_experts(
        tmp_path):
    """The tiny attn_moe configuration, with a model module and a reference
    kept with the tests: weights made and checked against the program's
    layout, and FLOPs that count the 2 routed experts and the shared one of
    each layer, not the 4 held."""
    cell = spec.load_cell(make_root(tmp_path, arch=TINY_MOE_ARCH), "tiny.chat")
    params = cell_run.build(cell, 2**31 + 11)
    moe = params["segments"][0][0]["moe"]
    assert moe["w_gate"].shape == (2, 4, 64, 48)
    assert moe["shared"]["w_up"].shape == (2, 64, 48)

    d, L, V, F = 64, 2, 256, 48
    attn = d * (4 + 2 * 2) * 16 + 4 * 16 * d
    router = d * 4
    expert = 3 * d * F
    routed = 2.0 * L * (attn + router + (2 + 1) * expert)
    held = 2.0 * L * (attn + router + (4 + 1) * expert)
    flops = cell.model.matmul_flops(cell.config, 10, 3)
    assert flops == 10 * routed + 3 * 2.0 * d * V
    assert flops < 10 * held
