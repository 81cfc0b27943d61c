"""chip_smoke.py off the chip: it refuses to run without a TPU, and its
phases — the same functions the chip run calls — pass at smoke size on the
CPU (the compiled-kernel check is the one part only a TPU can pass)."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402
from repro.configs.registry import get_config  # noqa: E402


def test_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                          cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr and "'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout


@pytest.fixture
def kernel_checks(monkeypatch):
    """Record the compiled-kernel check instead of asserting it: on the CPU
    the tick runs the XLA path or the interpreted kernel."""
    seen = []
    monkeypatch.setattr(chip_smoke, "check_compiled_kernel",
                        lambda name, eng, hlo: seen.append(
                            (name, eng.attn_backend,
                             "tpu_custom_call" in hlo)))
    return seen


def _smoke(arch, **kw):
    cfg = get_config(arch, smoke=True).replace(**kw)
    return cfg, chip_smoke.make_params(cfg, 0)


def test_one_chip_phase_at_smoke_size(kernel_checks):
    cfg, params = _smoke("h2o-danube-1.8b")
    traffic = chip_smoke.session_traffic(0, cfg.vocab_size, sessions=2,
                                         turns=2, system_len=32,
                                         suffix=(4, 40))
    chip_smoke.serve_one_chip(cfg, params, traffic, max_new=4, n_slots=4,
                              max_len=128, block_size=8, token_budget=32,
                              timeout_s=120.0)
    assert kernel_checks == [("bf16-pool", "xla", False),
                             ("int8-pool", "xla", False)]


def test_sliced_phase_runs_the_kernel_per_shard(kernel_checks):
    """A 1-device slice still traces under its mesh, so the interpreted
    kernel runs inside shard_map (make test-sharded adds wider slices)."""
    cfg, params = _smoke("h2o-danube-3-4b", attn_backend="pallas_interpret")
    traffic = chip_smoke.session_traffic(1, cfg.vocab_size, sessions=1,
                                         turns=2, system_len=16,
                                         suffix=(4, 12))
    devices = 2 if len(jax.devices()) >= 2 else 1
    chip_smoke.serve_sliced(cfg, params, traffic, max_new=3,
                            devices=devices, n_slots=2, max_len=64,
                            block_size=8, token_budget=16, timeout_s=120.0)
    assert [c[1] for c in kernel_checks] == ["pallas_interpret"] * 2


def test_pool_sized_collectives_flags_a_gathered_pool():
    hlo = "\n".join([
        "  %ag = bf16[4,1024,16,8,120]{4,3,2,1,0} all-gather(%p), dims={3}",
        "  %ar = bf16[1,256,3840]{2,1,0} all-reduce(%x), to_apply=%add",
        "  %cp = f32[8]{0} collective-permute-start(%y)",
    ])
    leaf = 1024 * 16 * 8 * 120
    assert len(chip_smoke.pool_sized_collectives(hlo, leaf)) == 1
    assert chip_smoke.pool_sized_collectives(hlo, 4 * leaf + 1) == []
