"""Model FLOPs of the traced rounds per second, as a share of the chip's
bf16 peak (``bench/peaks.json``, by ``device_kind``), in %.

A round's model FLOPs: three forward passes per real sample of every
modality each participant uploaded (forward and backward of one BGD step),
plus one forward pass per held-out sample and modality on rounds that
evaluated.  Padding, masked or non-uploading cohort slots, and the solver
are not counted.  The FLOPs per sample come from ``bench/flops/<config>.py``.
"""
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]


def peak_flops(kind: str) -> float:
    kinds = json.loads((BENCH / "peaks.json").read_text())["kinds"]
    if kind not in kinds:
        raise KeyError(f"no peak for device kind {kind!r} in peaks.json")
    return float(kinds[kind]["bf16_flops_per_s"])


def traced_flops(cfg, participants, fwd, seed):
    from bench.reference.inputs import make_inputs
    from bench.flops.layers import TRAIN_FACTOR
    inp = make_inputs(cfg, seed)
    n_test = len(inp.test.labels)
    total = 0.0
    for ok, evaluated in participants:
        for k in ok:
            c = inp.clients[k]
            total += TRAIN_FACTOR * c.size * sum(fwd[m] for m in c.modalities)
        if evaluated:
            total += n_test * sum(fwd.values())
    return total


def reduce(run, cfg, device):
    t = run.get("trace")
    if not t or not t["participants"]:
        return None
    from bench.run import load_module
    fwd = load_module(BENCH / "flops" / f"{cfg['name']}.py"
                      ).forward_per_sample(cfg)
    flops = traced_flops(cfg, t["participants"], fwd, run["seed"])
    return 100.0 * flops / t["window_s"] / peak_flops(device["kind"])
