"""Model FLOPs of the paper's submodels, from the configuration's shapes.

Counted as 2 x multiply-accumulates of the matrix products and
convolutions (taps that fall on a convolution's zero padding excluded), per
sample, forward pass; a training step costs three forward
passes' worth (forward, and the two products of the backward).  Elementwise
work (gates, activations, pooling, the loss) is left out, as model FLOPs
leave it out.
"""
from __future__ import annotations

import math


def lstm(spec: dict, n_classes: int) -> float:
    T, d, H = spec["T"], spec["d_in"], spec["hidden"]
    per_step = 2 * d * 4 * H + 2 * H * 4 * H       # layer 0: x@wi + h@wh
    per_step += 2 * H * 4 * H + 2 * H * 4 * H      # layer 1
    return T * per_step + 2 * H * H + 2 * H * n_classes


def same_taps(n: int, k: int) -> int:
    """Kernel taps that land inside the input, summed over the n outputs of
    a stride-1 SAME convolution along one axis (padding taps multiply
    zeros and are not counted)."""
    lo = (k - 1) // 2
    return sum(min(n - 1, o - lo + k - 1) - max(0, o - lo) + 1
               for o in range(n))


def cnn(spec: dict, n_classes: int) -> float:
    hw, ci, k, co = spec["hw"], spec["in_ch"], spec["kernel"], \
        spec["channels"]
    _, s = spec["pool"]
    flops = 0.0
    for _ in range(3):                  # SAME conv, then SAME pool stride s
        flops += 2 * same_taps(hw, k) ** 2 * ci * co
        hw, ci = math.ceil(hw / s), co
    f0, f1 = spec["fc"]
    flat = hw * hw * co
    return flops + 2 * flat * f0 + 2 * f0 * f1 + 2 * f1 * n_classes


def forward_per_sample(cfg: dict) -> dict:
    """{modality: forward FLOPs of one sample}."""
    out = {}
    for m, spec in cfg["models"].items():
        fn = lstm if spec["kind"] == "lstm" else cnn
        out[m] = float(fn(spec, cfg["n_classes"]))
    return out


TRAIN_FACTOR = 3.0
