"""A benchmark root in a temporary directory with one tiny cell, built from
files alone: the tests run the harness on it on the CPU.  Its model module
and reference are the benchmark's own, or ones kept with these tests
(``models/``, ``reference/`` here): a configuration of another family
comes in as new files, with nothing of the harness changed."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

TESTS = Path(__file__).resolve().parent
BENCH = TESTS.parent

TINY_ARCH = {
    "source": "tiny danube-shaped block for CPU tests",
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
    "vocab_size": 256, "sliding_window": 64, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-05, "max_position_embeddings": 256,
    "tie_word_embeddings": False, "torch_dtype": "float32",
    "model": "dense_gqa", "reference": "danube",
    "deployment": {"n_slots": 4, "max_len": 192, "num_blocks": None,
                   "kv_dtype": None, "devices_per_replica": None},
    "correct": {"max_logit_gap": 1e-3},
}

# the program's family "moe": every layer attention and a top-2 mixture of
# 4 experts with one shared expert
TINY_MOE_ARCH = dict(
    TINY_ARCH, source="tiny attn_moe block for CPU tests",
    num_local_experts=4, num_experts_per_tok=2, n_shared_experts=1,
    moe_intermediate_size=48, model="attn_moe", reference="attn_moe")

TINY_MIX = {
    "pool": 32, "span": 4,
    "sessions": {"arrival": "poisson", "rate_per_s": 4.0},
    "tenants": {"count": 2, "zipf_s": 1.0, "system_tokens": 32},
    "turns": {"dist": "geometric", "mean": 2.0, "max": 4},
    "user_tokens": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                    "min": 4, "max": 40},
    "output_tokens": {"dist": "lognormal", "median": 6, "sigma": 0.5,
                      "min": 2, "max": 12},
    "think_s": {"dist": "exponential", "mean": 0.05},
    "history": True, "context_margin": 16, "warm_s": 1.0,
}

# a metric that exists only in the temporary root
TINY_METRIC = '''
def read(ctx):
    ticks = ctx.delta("ticks")
    return ctx.delta("tokens_out") / ticks if ticks else None
'''


def _source(sub: str, name: str) -> Path:
    """``<sub>/<name>.py`` of the benchmark, else of these tests."""
    for d in (BENCH, TESTS):
        if (d / sub / f"{name}.py").is_file():
            return d / sub / f"{name}.py"
    raise FileNotFoundError(f"no {sub}/{name}.py")


def make_root(tmp: Path, arch: dict = TINY_ARCH, mix: dict = TINY_MIX) -> Path:
    """A checkout-like root: BENCHMARK.json and ``tiny/`` with a config, a
    mix, a metric reader and copies of the model module and reference the
    config names."""
    d = tmp / "tiny"
    for sub in ("configs", "traffic", "metrics", "models", "reference"):
        (d / sub).mkdir(parents=True, exist_ok=True)
    (d / "configs" / "tiny-danube.json").write_text(json.dumps(arch))
    (d / "traffic" / "tiny_chat.json").write_text(json.dumps(mix))
    (d / "metrics" / "tokens_per_tick.tiny.py").write_text(TINY_METRIC)
    for name in ("ttft_p95_s.py", "setup_s.py"):
        shutil.copy(BENCH / "metrics" / name, d / "metrics" / name)
    for sub, key in (("models", "model"), ("reference", "reference")):
        shutil.copy(_source(sub, arch[key]), d / sub / f"{arch[key]}.py")
    spec = {
        "command": ["python3", "bench/run.py"], "paths": ["tiny"],
        "run_seconds": 2,
        "configs": [{"name": "tiny-danube", "source": "test",
                     "file": "tiny/configs/tiny-danube.json", "reduced": [],
                     "why": "test"}],
        "workloads": [{"name": "tiny.chat", "config": "tiny-danube",
                       "traffic": "tiny_chat", "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": "ttft_p95_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": [
            {"name": "tokens_per_tick.tiny", "unit": "tokens",
             "better": "higher", "source": "program_counter",
             "layer": "engine tick", "moves": "ttft_p95_s"}],
    }
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp
