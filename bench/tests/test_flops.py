"""The FLOP function against XLA's own count of one sample's forward pass
of each submodel (recurrences unrolled, so every step is counted)."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench.flops.layers import TRAIN_FACTOR, forward_per_sample, same_taps
from bench.reference import model as Mo

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def test_same_taps_by_hand():
    # 5 taps centred on each of 4 outputs: 3 + 4 + 4 + 3 inside the input
    assert same_taps(4, 5) == 14
    assert same_taps(32, 1) == 32


@pytest.mark.parametrize("name", ["crema_d-paper", "iemocap-paper"])
def test_forward_flops_match_xla(name):
    cfg = json.loads((CONFIGS / f"{name}.json").read_text())
    params = Mo.init_params(cfg, 0)
    fwd = forward_per_sample(cfg)
    for m, spec in cfg["models"].items():
        if spec["kind"] == "lstm":
            x = jnp.zeros((1, spec["T"], spec["d_in"]))

            def fn(p, xx, spec=spec):
                return Mo.lstm_logits(p, xx, spec, unroll=True)
        else:
            x = jnp.zeros((1, spec["hw"], spec["hw"], spec["in_ch"]))

            def fn(p, xx, spec=spec):
                return Mo.cnn_logits(p, xx, spec)
        cost = jax.jit(fn).lower(params[m], x).compile().cost_analysis()
        cost = cost[0] if isinstance(cost, list) else cost
        # XLA also counts the elementwise work model FLOPs leave out
        assert 1.0 <= cost["flops"] / fwd[m] <= 1.1, (m, cost["flops"],
                                                      fwd[m])
    assert TRAIN_FACTOR == 3.0


def test_peaks_table_has_the_v5e_and_refuses_unknown_kinds():
    from bench.metrics import round_mfu
    assert round_mfu.peak_flops("TPU v5 lite") == 197e12
    with pytest.raises(KeyError):
        round_mfu.peak_flops("cpu")
