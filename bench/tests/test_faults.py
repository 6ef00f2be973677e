"""``correct`` comes out false when the timed path is broken underneath,
and the control (the reference one precision step lower) fails the check
(on a TPU: the CPU computes every matmul precision alike).

Each fault is planted in the program for the duration of one tiny CPU run:

* a step that returns its state unchanged;
* half of each client's batch left out, the mean taken over the rest;
* the schedule altered where the policy produces it.

(The cells run on one chip, so there is no exchange between chips to
leave out.)
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check
from bench import readings as Rd
from bench import run as B
from bench.tests.test_rehearsal import tiny


def _run(cell, tmp_path, seed=4):
    spec, c, cfg, traffic = tiny(cell)
    return B.execute(spec, c, cfg, traffic, seed=seed, seconds=0.2,
                     trace=False, out_dir=tmp_path, t_start=time.time())


def _unchanged(monkeypatch):
    from repro.fl.fused_round import FusedRoundEngine
    run = FusedRoundEngine.run

    def stuck(self, carry, xs, scanned):
        _, aux, wall = run(self, carry, xs, scanned)
        return carry, aux, wall
    monkeypatch.setattr(FusedRoundEngine, "run", stuck)


def _half_batch(monkeypatch):
    from repro.data.partition import ClientStore
    take = ClientStore.take

    def half(self, idx):
        c = take(self, idx)
        n = c.sample_mask.shape[1]
        keep = jnp.arange(n)[None, :] < (c.sizes // 2)[:, None]
        import dataclasses
        return dataclasses.replace(c, sample_mask=c.sample_mask * keep)
    monkeypatch.setattr(ClientStore, "take", half)


def _altered(monkeypatch):
    from repro.wireless.policies import SchedulePolicy
    finish = SchedulePolicy._finish

    def flip(self, state, a, B, J, drop=None):
        return finish(self, state, a.at[0].set(~a[0]), B, J, drop)
    monkeypatch.setattr(SchedulePolicy, "_finish", flip)


@pytest.mark.parametrize("cell,plant", [
    ("crema_d-paper.jcsba-scan", _unchanged),
    ("crema_d-paper.jcsba-scan", _half_batch),
    ("crema_d-paper.jcsba-scan", _altered),
    ("iemocap-paper.jcsba-scan", _unchanged),
])
def test_fault_makes_correct_false(cell, plant, monkeypatch, tmp_path):
    plant(monkeypatch)
    out = _run(cell, tmp_path)
    assert out["correct"] is False, out["check"]


needs_tpu = pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="the control lowers the matmul precision, which the CPU ignores")


@needs_tpu
@pytest.mark.parametrize("cell", ["crema_d-paper.jcsba-scan",
                                  "iemocap-paper.jcsba-scan"])
def test_control_fails_a_limit(cell):
    _, c, cfg, traffic = tiny(cell)
    limits = check.limits_for(c["name"])
    for seed in (11, 12, 13):
        rd = Rd.readings(cfg, traffic, seed, "control")
        failed = [k for k, lim in limits.items()
                  if not float(rd[k]) <= lim]
        assert failed, (seed, rd)


def test_stand_in_faults_fail_a_limit():
    _, c, cfg, traffic = tiny("crema_d-paper.jcsba-scan")
    limits = check.limits_for(c["name"])
    for what in ("unchanged", "half_batch", "altered"):
        rd = Rd.readings(cfg, traffic, 11, what)
        assert any(not float(rd[k]) <= lim for k, lim in limits.items()), \
            (what, rd)
    assert np.isfinite(limits["dparam_gap"])
