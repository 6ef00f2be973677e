"""The harness end to end on the CPU at a tiny size: K=4 clients, 300
samples, the default ``fused`` XLA path.  ``run.execute`` is everything a
run does after ``main``'s look for a chip."""
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

from bench import run as B

ROOT = Path(__file__).resolve().parents[2]
TINY = dict(K=4, n_samples=300)
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def tiny(cell: str):
    """(spec, cell, configuration at K=4 and 300 samples, mix) of a cell
    named ``<config>.<traffic>``, in ``BENCHMARK.json`` or not."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    config, traffic = cell.split(".")
    cfg = json.loads((ROOT / "bench/configs" / f"{config}.json").read_text())
    mix = json.loads((ROOT / "bench/traffic" / f"{traffic}.json").read_text())
    c = {"name": cell, "config": config, "traffic": traffic, "chips": 1}
    return spec, c, dict(cfg, **TINY), mix


@pytest.mark.parametrize("cell", ["crema_d-paper.jcsba-scan",
                                  "iemocap-paper.jcsba-scan"])
def test_scanned_driver_last_line(cell, tmp_path):
    spec, c, cfg, traffic = tiny(cell)
    out = B.execute(spec, c, cfg, traffic, seed=2 ** 31 + 12345,
                    seconds=0.5, trace=False, out_dir=tmp_path,
                    t_start=time.time())
    assert KEYS <= set(out) and list(out)[-1] == "check"
    json.dumps(out, allow_nan=False)
    assert out["correct"] is True, out["check"]
    assert out["failed"] == 0 and out["attempted"] >= 10
    assert set(out["metrics"]) == {"rounds_per_s", "setup_s"}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert set(out["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "crema_d-paper.jcsba-scan", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr
