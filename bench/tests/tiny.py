"""A benchmark root in a temporary directory with one tiny cell, built from
files alone: the tests run the harness on it on the CPU."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]

TINY_ARCH = {
    "source": "tiny danube-shaped block for CPU tests",
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
    "vocab_size": 256, "sliding_window": 64, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-05, "max_position_embeddings": 256,
    "tie_word_embeddings": False, "torch_dtype": "float32",
    "reference": "danube",
    "deployment": {"n_slots": 4, "max_len": 192, "num_blocks": None,
                   "kv_dtype": None, "devices_per_replica": None},
    "correct": {"max_logit_gap": 1e-3},
}

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


def make_root(tmp: Path, arch: dict = TINY_ARCH, mix: dict = TINY_MIX) -> Path:
    """A checkout-like root: BENCHMARK.json and ``tiny/`` with a config, a
    mix, a metric reader and a copy of the reference."""
    d = tmp / "tiny"
    for sub in ("configs", "traffic", "metrics", "reference"):
        (d / sub).mkdir(parents=True, exist_ok=True)
    (d / "configs" / "tiny-danube.json").write_text(json.dumps(arch))
    (d / "traffic" / "tiny_chat.json").write_text(json.dumps(mix))
    (d / "metrics" / "tokens_per_tick.tiny.py").write_text(TINY_METRIC)
    for name in ("ttft_p95_s.py", "setup_s.py"):
        shutil.copy(BENCH / "metrics" / name, d / "metrics" / name)
    shutil.copy(BENCH / "reference" / "danube.py",
                d / "reference" / "danube.py")
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
