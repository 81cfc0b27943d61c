"""A whole run of a tiny cell on the CPU, past the harness's look for a
chip: it comes out correct, and with the timed path broken it does not."""
import json
import os
import subprocess
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench.harness import cell_run, correct, spec
from bench.harness.cell_run import run_cell
from bench.tests.tiny import TINY_ARCH, TINY_MOE_ARCH, make_root

ROOT = Path(__file__).resolve().parents[2]


# the tiny cell as its dense configuration, and as one of another family
# that brings its own model module and reference
ARCHS = pytest.mark.parametrize("arch", [TINY_ARCH, TINY_MOE_ARCH],
                                ids=["dense_gqa", "attn_moe"])


def run(tmp_path, seed=2**31 + 3, arch=TINY_ARCH):
    cell = spec.load_cell(make_root(tmp_path, arch=arch), "tiny.chat")
    return run_cell(cell, seed, 2.0, False, t_proc0=time.monotonic())


@ARCHS
def test_tiny_cell_runs_correct(tmp_path, capsys, arch):
    out = run(tmp_path, arch=arch)
    err = capsys.readouterr().err.splitlines()
    assert any(line.startswith("longest node.step inside the window: ")
               for line in err)
    assert [line.split(":")[0] for line in err[-2:]] == [
        "check max_logit_gap", "check failed_requests"]
    assert out["correct"] is True
    assert out["attempted"] > 5 and out["failed"] == 0
    assert set(out["metrics"]) == {"ttft_p95_s", "setup_s"}
    assert list(out)[-1] == "checks"
    json.loads(json.dumps(out, allow_nan=False))
    assert out["checks"]["max_logit_gap"]["value"] <= 1e-3


def test_the_control_is_not_correct(tmp_path):
    """The reference computed at fp8, in the program's place, on the
    window's own served requests, reads past the limit the program keeps
    (what bench/control.py measures on the chip at the cells' sizes)."""
    cell = spec.load_cell(make_root(tmp_path), "tiny.chat")
    seed = 17
    params = cell_run.build(cell, seed)
    served = cell_run.serve(cell, params, seed, 2.0, False,
                            t_proc0=time.monotonic(),
                            compiles=cell_run.CompileCounter())
    sample = correct.sample(served.drive.finished_in_window(), seed)
    ref = spec.reference(cell)
    limit = cell.config["correct"]["max_logit_gap"]
    assert correct.widest_gap(ref, params, cell.config, sample) <= limit
    assert correct.control_gap(ref, params, cell.config, sample) > limit


def _rec(rid, prompt_len, n_tokens):
    return SimpleNamespace(request_id=rid,
                           turn=SimpleNamespace(prompt=[0] * prompt_len),
                           req=SimpleNamespace(tokens=[1] * n_tokens))


def test_the_sample_spans_several_requests_and_holds_the_longest():
    done = [_rec(f"r{i}", 100 + i, 10) for i in range(8)]
    done.append(_rec("long", 900, 500))       # alone covers min_tokens
    for seed in (1, 2**31 + 5):
        picked = correct.sample(done, seed, min_tokens=320, min_requests=4)
        assert picked[0].request_id == "long"
        assert len(picked) == 4 and len({r.request_id for r in picked}) == 4
        picked = correct.sample(done, seed, min_tokens=560, min_requests=2)
        assert sum(len(r.req.tokens) for r in picked) >= 560
    # fewer finished than the minimum: every one of them
    assert len(correct.sample(done[:3], 7, min_tokens=1,
                              min_requests=6)) == 3
    assert correct.sample([], 7, min_tokens=1, min_requests=6) == []


@ARCHS
def test_an_altered_token_is_not_correct(tmp_path, monkeypatch, arch):
    import repro.serving.engine as engine

    real = engine.speculative_verify

    def altered(logits, *a, **k):
        tok, n_acc, score = real(logits, *a, **k)
        return (tok + 1) % logits.shape[-1], n_acc, score

    monkeypatch.setattr(engine, "speculative_verify", altered)
    out = run(tmp_path, arch=arch)
    assert out["correct"] is False
    assert out["checks"]["max_logit_gap"]["value"] > 1e-3


def test_a_tick_that_leaves_the_kv_state_unchanged_is_not_correct(
        tmp_path, monkeypatch):
    import repro.serving.engine as engine

    real = engine.paged_mixed_step

    def stale(params, pools, *a, **k):
        logits, _ = real(params, pools, *a, **k)
        return logits, pools

    monkeypatch.setattr(engine, "paged_mixed_step", stale)
    out = run(tmp_path)
    assert out["correct"] is False


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "danube18b.chat", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "accelerator" in p.stderr
