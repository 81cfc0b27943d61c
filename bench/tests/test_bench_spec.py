"""Cells are found by name from files alone, and the benchmark's names and
units keep to the characters allowed."""
import json
import re
from pathlib import Path

import pytest

from bench.harness import spec
from bench.harness.peaks import peak
from bench.tests.tiny import TINY_ARCH, TINY_MOE_ARCH, make_root

ROOT = Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def benchmark():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


MODEL_FUNCTIONS = ("dims", "program_config", "weight_shapes", "matmul_flops")


@pytest.mark.parametrize("arch", [TINY_ARCH, TINY_MOE_ARCH],
                         ids=["dense_gqa", "attn_moe"])
def test_a_cell_from_files_alone_is_found_by_name(tmp_path, arch):
    root = make_root(tmp_path, arch=arch)
    cell = spec.load_cell(root, "tiny.chat")
    assert cell.config["hidden_size"] == 64
    assert all(callable(getattr(cell.model, f)) for f in MODEL_FUNCTIONS)
    assert cell.model.dims(cell.config)["V"] == 256
    assert cell.traffic["sessions"]["arrival"] == "poisson"
    assert [m["name"] for m in cell.per_layer] == ["tokens_per_tick.tiny"]
    read = spec.metric_reader(cell, "tokens_per_tick.tiny")

    class Ctx:
        def delta(self, key):
            return {"tokens_out": 12, "ticks": 4}[key]

    assert read(Ctx()) == 3.0
    assert hasattr(spec.reference(cell), "logits")
    with pytest.raises(KeyError):
        spec.load_cell(root, "no.such.cell")


def _without(d: dict, key: str) -> dict:
    return {k: v for k, v in d.items() if k != key}


@pytest.mark.parametrize("arch,reduced,error,named", [
    (_without(TINY_ARCH, "model"), [], KeyError, "'model'"),
    (dict(TINY_ARCH, model="no_such_model"), [], FileNotFoundError,
     "no_such_model"),
    (dict(TINY_ARCH, reference="no_such_ref"), [], FileNotFoundError,
     "no_such_ref"),
    (TINY_MOE_ARCH, ["num_local_experts"], ValueError, "num_local_experts"),
], ids=["no_model_key", "no_model_file", "no_reference_file", "cut_unstated"])
def test_what_a_configuration_names_and_lacks_fails_by_name(
        tmp_path, arch, reduced, error, named):
    root = make_root(tmp_path)
    (root / "tiny" / "configs" / "tiny-danube.json").write_text(
        json.dumps(arch))
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"][0]["reduced"] = reduced
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    with pytest.raises(error, match=named):
        spec.reference(spec.load_cell(root, "tiny.chat"))


def test_a_stated_cut_is_taken(tmp_path):
    arch = dict(TINY_MOE_ARCH, cut={"num_local_experts": {
        "published": 32, "deployment": "8 chips share each expert layer"}})
    root = make_root(tmp_path, arch=arch)
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"][0]["reduced"] = ["num_local_experts"]
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    assert spec.load_cell(root, "tiny.chat").config["num_local_experts"] == 4


def test_names_and_units_use_only_the_allowed_characters():
    b = benchmark()
    names = ([c["name"] for c in b["configs"]]
             + [w["name"] for w in b["workloads"]]
             + [w["traffic"] for w in b["workloads"]]
             + [m["name"] for m in b["end_to_end"] + b["per_layer"]]
             + [k for c in b["configs"] for k in c["reduced"]])
    assert all(NAME.match(n) for n in names), names
    assert all(UNIT.match(m["unit"]) for m in b["end_to_end"] + b["per_layer"])
    for text in ([w["why"] for w in b["workloads"]]
                 + [m["layer"] for m in b["per_layer"]]
                 + [c["source"] for c in b["configs"]] + b["command"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_every_cell_finds_its_files_and_metric_readers():
    b = benchmark()
    for w in b["workloads"]:
        cell = spec.load_cell(ROOT, w["name"])
        assert all(callable(getattr(cell.model, f)) for f in MODEL_FUNCTIONS)
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2 and cell.per_layer
        for m in cell.end_to_end + cell.per_layer:
            assert callable(spec.metric_reader(cell, m["name"]))
        for m in cell.per_layer:
            assert m["moves"] in e2e, (w["name"], m["name"])


def test_peaks_are_keyed_by_device_kind_and_unknown_kinds_fail():
    assert peak("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert "TPU v5e" in peak("TPU v5 lite")["source"]
    with pytest.raises(KeyError):
        peak("cpu")
