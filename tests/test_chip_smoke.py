"""CPU guard for ``chip_smoke.py`` and the compile-cache helper.

The smoke script's phases run here at the reduced ``cifar_cnn`` width,
so the comparisons they make cannot rot between chip runs.  On the CPU
every path is float32-exact, so the bounds here are tighter than the
chip's.  The script's ``main`` itself must refuse the CPU.
"""

from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys
import textwrap

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402

CPU_BOUND = 1e-5


def _spec():
    return chip_smoke.paper_spec(model="cifar_cnn", rounds=2, chunk=1,
                                 local_steps=2, batch_size=8,
                                 data_size=1000, eval_size=100)


def _env(**extra):
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu")
    env.update(extra)
    return env


def test_kernel_phase_on_cpu():
    segments = chip_smoke.model_segments("cifar_cnn")
    errs = chip_smoke.kernel_phase(segments)
    # every kernel and its last tile, row_stream over the real segments
    assert {"fused_aggregate/tail", "row_stream", "row_stream/tail",
            "fused_memory_update.contrib/tail", "fused_dequant_aggregate",
            "block_fused_aggregate/tail"} <= set(errs)
    assert max(errs.values()) < CPU_BOUND


def test_trainer_phase_on_cpu():
    out = chip_smoke.trainer_phase(_spec())
    assert out["losses"].shape == (2,) and np.all(np.isfinite(out["losses"]))
    assert out["rounds_per_s"] > 0
    assert not out["pallas"]  # the CPU contracts in jnp


def test_path_agreement_phase_on_cpu():
    assert chip_smoke.path_agreement_phase(_spec(), bound=CPU_BOUND) < CPU_BOUND


def test_failed_check_raises():
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.kernel_phase(chip_smoke.model_segments("cifar_cnn"),
                                bound=0.0)
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.check("nan", float("nan"), 1.0)


def test_main_refuses_cpu():
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         capture_output=True, text=True, timeout=300,
                         cwd=ROOT, env=_env())
    assert out.returncode != 0
    assert "platform is 'cpu'" in out.stderr
    assert '"ok"' not in out.stdout


@pytest.mark.slow
def test_four_chip_phase_on_virtual_devices():
    script = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {str(ROOT)!r})
        import chip_smoke
        err = chip_smoke.four_chip_phase(model="cifar_cnn", local_steps=2,
                                         batch_size=8, bound={CPU_BOUND})
        print("FOUR_CHIP_ERR", err)
    """)
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=600, cwd=ROOT,
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert out.returncode == 0, out.stdout + out.stderr[-3000:]
    assert "FOUR_CHIP_ERR" in out.stdout


def test_compile_cache_default_dir_is_fixed_and_ignored():
    from repro.compile_cache import DEFAULT_CACHE_DIR

    assert DEFAULT_CACHE_DIR == ROOT / ".jax_cache"
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_entries_land_in_one_dir(tmp_path, from_env):
    env_dir, default_dir = tmp_path / "env", tmp_path / "default"
    script = textwrap.dedent(f"""
        import json, jax, jax.numpy as jnp
        from repro import compile_cache
        compile_cache.DEFAULT_CACHE_DIR = {str(default_dir)!r}
        path = compile_cache.enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()
        print(json.dumps(str(path)))
    """)
    env = _env()
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, timeout=300, cwd=tmp_path, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    used, unused = (env_dir, default_dir) if from_env else (default_dir, env_dir)
    assert json.loads(out.stdout.strip().splitlines()[-1]) == str(used)
    assert any(used.iterdir()) and not unused.exists()
