"""The ``scanned`` driver's window replays the checked warm-up call: every
replayed ``run_scanned(R)`` gives the warm-up call's records bit for bit,
and a replay that departs from it counts as failed (CPU, tiny size)."""
import time

import jax
import numpy as np
import pytest

from bench import run as B
from bench.drivers import scanned as S
from bench.tests.test_rehearsal import tiny

CELLS = ["crema_d-paper.jcsba-scan", "iemocap-paper.jcsba-scan"]


def _metrics(recs):
    return np.array([[r.metrics[k] for k in sorted(r.metrics)]
                     for r in recs])


@pytest.mark.parametrize("cell", CELLS)
def test_replay_gives_the_warm_up_call_again(cell):
    _, _, cfg, traffic = tiny(cell)
    R = traffic["rounds_per_call"]
    with jax.default_matmul_precision(cfg["precision"]["matmul"]):
        exp = S.build(cfg, traffic, seed=2 ** 31 + 7)
        exp._get_fused_engine()
        kept = S.keep(exp)
        record = S.first_call(exp, R)
        warm = list(exp.history)
        for _ in range(2):
            S.replay(exp, kept)
            recs = exp.run_scanned(R)
            assert [r.participants for r in recs] == \
                [r.participants for r in warm]
            assert [r.failures for r in recs] == [r.failures for r in warm]
            np.testing.assert_array_equal(_metrics(recs), _metrics(warm))
            assert exp._round == R and len(exp.history) == R
            assert S._failed_rounds(recs, record) == 0


@pytest.mark.parametrize("cell", CELLS)
def test_replay_that_departs_counts_as_failed(cell, monkeypatch, tmp_path):
    from repro.fl.fused_round import FusedRoundEngine
    run = FusedRoundEngine.run
    calls = []

    def flip_after_first(self, carry, xs, scanned):
        carry, aux, round0 = run(self, carry, xs, scanned)
        calls.append(round0)
        if len(calls) > 1:          # client 0 in or out of round 0
            a, ok = np.array(aux.a), np.array(aux.ok)
            a[0, 0] = ok[0, 0] = ~a[0, 0]
            aux = aux._replace(a=a, ok=ok)
        return carry, aux, round0
    monkeypatch.setattr(FusedRoundEngine, "run", flip_after_first)

    spec, c, cfg, traffic = tiny(cell)
    out = B.execute(spec, c, cfg, traffic, seed=5, seconds=0.2,
                    trace=False, out_dir=tmp_path, t_start=time.time())
    assert len(calls) > 1
    assert out["failed"] > 0 and out["correct"] is False
