"""`chip_smoke.py` rehearsed on the CPU: every phase function at smoke size
(Pallas kernels interpreted, "chip" and "CPU" the same host device), the
four-chip phase on four virtual CPU devices in a child process, the script's
refusal to run without a TPU, and where the compile cache lands.

The phases' checks are the script's own; here they must pass at a size that
runs in seconds.  Nothing here says anything about the chip.
"""
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402
from repro.launch import cache  # noqa: E402

SERVE = dict(slots=2, cache_len=48, prompt_lens=(8, 16, 24), gen=4,
             stagger=1)
SMOKE_SIZES = {
    "train": dict(smoke=True, clients=2, local_steps=2, batch=2, seq=16,
                  rounds=3),
    "train_parity": dict(clients=2, local_steps=2, batch=1, seq=16),
    "fleet": dict(n=1000, rounds=2, n_host=1000),
    "serve": dict(smoke=True, **SERVE),
    "engine_parity": SERVE,
}


def test_smoke_sizes_cover_every_phase():
    assert set(SMOKE_SIZES) == {name for name, _ in
                                chip_smoke.ONE_CHIP_PHASES}


@pytest.mark.parametrize("name", list(SMOKE_SIZES))
def test_phase_passes_at_smoke_size(name):
    dict(chip_smoke.ONE_CHIP_PHASES)[name](**SMOKE_SIZES[name])


def test_four_chip_phase_on_four_cpu_devices():
    code = ("import chip_smoke; chip_smoke.phase_four_chips(n=2000, "
            "rounds=2, smoke=True, clients=4, local_steps=2, batch=1, "
            "seq=8)")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert "FAILED" not in proc.stdout
    assert "spans 4 devices: passed" in proc.stdout


def test_main_refuses_a_host_without_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main([]) == 1
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "needs a TPU" in out.err


def test_compile_cache_defaults_to_the_repo(monkeypatch):
    was = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        assert cache.enable_compile_cache() == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(
            ROOT / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_lands_where_the_environment_says(tmp_path):
    code = ("import jax; from repro.launch.cache import enable_compile_cache;"
            "print(enable_compile_cache());"
            "jax.jit(lambda x: x * 2 + 1)(1.0).block_until_ready()")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               PYTHONPATH=str(ROOT / "src"))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert proc.stdout.strip() == str(tmp_path)
    assert any(tmp_path.iterdir()), "nothing was cached"
