"""The fused round's trace names: stage scopes inside the round program and
host spans around each call.

* every op of ``FusedRoundEngine._round_step`` sits under ``round/<stage>``
  (policy, gather, bgd, aggregate, eval), so a profiler trace of any entry
  that runs the body splits device time by stage;
* ``run_scanned`` and ``run_round`` leave one ``mfl.call`` span per call,
  holding ``mfl.draw_xs``, ``mfl.dispatch``, ``mfl.fetch``, ``mfl.decode``
  and ``mfl.export``, each tagged with the call's first round ``round0``;
* fused records carry no wall time (``sched_time_s`` is NaN), and
  ``final_metrics`` reports a mean scheduler time only for host engines.
"""
import dataclasses
import json
import math
import re

import jax
import pytest

from repro.fl.runtime import MFLExperiment

CFG = dict(n_samples=200, seed=3, eval_every=1)
STAGES = ("policy", "gather", "bgd", "aggregate", "eval")
PHASES = ("mfl.draw_xs", "mfl.dispatch", "mfl.fetch", "mfl.decode",
          "mfl.export")


@pytest.fixture(scope="module")
def scan_hlo():
    """The compiled text of a 2-round scan of the fused round program."""
    from repro.fl.fused_round import draw_round_xs
    exp = MFLExperiment(dataset="iemocap", engine="fused", **CFG)
    eng = exp._get_fused_engine()
    xs = draw_round_xs(exp, 2)
    return eng.lower(exp._carry, xs).compile().as_text()


@pytest.mark.parametrize("stage", STAGES)
def test_compiled_scan_carries_stage_scope(scan_hlo, stage):
    names = re.findall(r'op_name="([^"]*)"', scan_hlo)
    assert any(f"/round/{stage}/" in n for n in names), stage
    # the scan's own loop body holds the round: the scope survives lax.scan
    assert any("/while/body/" in n and f"round/{stage}/" in n
               for n in names), stage


def _host_spans(trace_dir):
    """(name, start, end, stats) of every ``mfl.*`` or harness-named host
    event in the trace under ``trace_dir``."""
    from pathlib import Path
    from jax.profiler import ProfileData
    path = sorted(Path(trace_dir).glob("**/*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("mfl.") or ev.name in (
                        "run_scanned", "harness"):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return out


@pytest.mark.parametrize("entry", ["run_scanned", "run_round"])
def test_host_spans_nest_per_call(tmp_path, entry):
    exp = MFLExperiment(dataset="iemocap", engine="fused", **CFG)

    def call():
        return exp.run_scanned(2) if entry == "run_scanned" else \
            [exp.run_round()]

    call()                                  # compiles outside the trace
    firsts = []
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(2):
            firsts.append(exp._round)
            call()
    spans = _host_spans(tmp_path)
    names = {s[0] for s in spans}
    assert not names & {"run_scanned", "harness"}
    calls = sorted(s for s in spans if s[0] == "mfl.call")
    assert [c[3]["round0"] for c in calls] == firsts
    for _, lo, hi, stats in calls:
        inside = sorted((s for s in spans if s[0] != "mfl.call"
                         and s[3]["round0"] == stats["round0"]),
                        key=lambda s: s[1])
        assert [s[0] for s in inside] == list(PHASES)
        assert all(lo <= s[1] <= s[2] <= hi for s in inside)
        # the phases follow one another without overlapping
        assert all(a[2] <= b[1] for a, b in zip(inside, inside[1:]))


def test_fused_records_carry_no_wall_time():
    fus = MFLExperiment(dataset="iemocap", engine="fused", **CFG)
    recs = fus.run_scanned(2) + [fus.run_round()]
    assert all(math.isnan(r.sched_time_s) for r in recs)
    json.loads(json.dumps([dataclasses.asdict(r) for r in recs]))
    assert "mean_sched_time_s" not in fus.final_metrics()
    assert "multimodal" in fus.final_metrics()

    host = MFLExperiment(dataset="iemocap", engine="batched", **CFG)
    host.run(1)
    assert math.isfinite(host.history[0].sched_time_s)
    assert host.final_metrics()["mean_sched_time_s"] >= 0.0
