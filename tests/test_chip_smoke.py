"""The chip smoke script's phases, driven on the CPU at a tiny size.

Kernels run in interpret mode here (the backend is not a TPU), so each
kernel phase is told ``interpret=True`` and checks that its compiled round
program holds no ``tpu_custom_call``; on the chip the same check demands
one.  ``main()`` itself must refuse to run without a TPU.
"""
import dataclasses
import json
import os
import subprocess
import sys
import types
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402

TINY = dict(dataset="crema_d", scheduler="jcsba", K=4, n_samples=80)


@pytest.fixture(scope="module")
def paper():
    exp, row = chip_smoke.phase_paper(TINY, rounds=1)
    return exp, row


def test_phase_paper_matches_batched_twin(paper):
    _, row = paper
    assert row["reference"] == "batched"
    assert row["max_param_diff"] <= chip_smoke.PARAM_TOL
    assert not row["tpu_custom_call"]
    assert set(row["final_metrics"]) == {"multimodal", "loss", "audio",
                                         "image"}


def test_phase_paper_pallas_interpreted(paper):
    exp, _ = paper
    row = chip_smoke.phase_paper_pallas(TINY, exp, interpret=True, rounds=1)
    assert row["max_param_diff"] <= chip_smoke.PARAM_TOL
    assert not row["tpu_custom_call"]


def test_phase_paper_pallas_demands_kernel(paper):
    """Told the kernel runs compiled, the phase fails when the round
    program has no ``tpu_custom_call`` — the check the chip run relies on."""
    exp, _ = paper
    with pytest.raises(chip_smoke.SmokeFailure, match="tpu_custom_call"):
        chip_smoke.phase_paper_pallas(TINY, exp, interpret=False, rounds=1)


@pytest.mark.parametrize("arch", ["transformer", "ssd"])
def test_phase_backbone_interpreted(arch):
    cfg = dict(TINY, n_samples=40)
    row = chip_smoke.phase_backbone(arch, cfg, interpret=True)
    assert row["phase"] == f"{arch}_pallas"
    assert row["max_param_diff"] <= chip_smoke.PARAM_TOL


def test_compare_rejects_different_participants(paper):
    exp, _ = paper
    other = types.SimpleNamespace(
        history=[dataclasses.replace(r, participants=r.participants + [99])
                 for r in exp.history],
        global_params=exp.global_params)
    with pytest.raises(chip_smoke.SmokeFailure, match="participant"):
        chip_smoke._compare("x", exp, other)


@pytest.mark.parametrize("argv", [[], ["--four-chips"]])
def test_main_refuses_without_tpu(argv, capsys):
    assert chip_smoke.main(argv) != 0
    assert '"ok": true' not in capsys.readouterr().out


def test_four_chip_phase_on_virtual_devices():
    """The sharded sweep phase on four virtual CPU devices (own process:
    the device count is fixed when JAX starts)."""
    code = ("import chip_smoke; chip_smoke.phase_four_chips("
            f"{TINY!r}, rounds=1)")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(REPO / "src"))
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    rows = [json.loads(line) for line in out.stdout.splitlines()
            if line.startswith("{")]
    shards = [r for r in rows if "shards" in r]
    assert {r["shards"] for r in shards} == {"store.features.audio",
                                             "xs.client_seeds"}
    for r in shards:
        assert len({d for d, _ in r["placement"]}) == 4
    final = rows[-1]
    assert final["phase"] == "four_chips"
    assert final["max_param_diff"] <= chip_smoke.PARAM_TOL


@pytest.mark.parametrize("env_dir", [True, False])
def test_compile_cache_location(env_dir, tmp_path):
    """JAX_COMPILATION_CACHE_DIR, when set, is left to JAX and receives
    the entries; otherwise the cache is the fixed in-checkout directory."""
    code = (
        "import jax, jax.numpy as jnp\n"
        "from repro.launch.compile_cache import enable_compile_cache, "
        "REPO_CACHE_DIR\n"
        "d = enable_compile_cache()\n"
        "print(d, REPO_CACHE_DIR, jax.config.jax_compilation_cache_dir)\n")
    if env_dir:
        code += ("jax.config.update("
                 "'jax_persistent_cache_min_compile_time_secs', 0)\n"
                 "jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO / "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    used, repo_dir, configured = out.stdout.split()
    assert repo_dir == str(REPO / ".jax_cache")
    if env_dir:
        assert used == configured == str(tmp_path)
        assert any(tmp_path.iterdir())
    else:
        assert used == configured == repo_dir
