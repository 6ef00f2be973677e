"""The reduction from a trace to per-stage device time and per-phase idle
time (``bench/scopes.py``), on hand-made events and a hand-made XSpace."""
import pytest

from bench import scopes as S

DEV = "/device:TPU:0"


def test_stage_of_reads_the_scope_in_an_op_path():
    assert S.stage_of("jit(_scan_steps)/while/body/closed_call/round/bgd/"
                      "cond/branch_1_fun/dot_general") == "bgd"
    assert S.stage_of("round/eval/cond") == "eval"
    assert S.stage_of("jit(_scan_steps)/while") == S.UNSCOPED
    assert S.stage_of("around/policy/x") == S.UNSCOPED
    assert S.stage_of("round/policyx/y") == S.UNSCOPED


def test_self_time_goes_to_the_scope_and_nested_ops_are_subtracted():
    # a while loop (unscoped) holding a bgd conditional that holds a
    # policy-scoped op: each keeps only its own time
    ops = {DEV: [("unscoped", 0, 100, "while.1"),
                 ("bgd", 10, 60, "conditional.2"),
                 ("bgd", 12, 30, "fusion.3"),
                 ("policy", 35, 45, "fusion.4"),
                 ("eval", 70, 90, "fusion.5")]}
    t = S.stage_times(ops, 0, 100)
    assert t == {"unscoped": 100 - 50 - 20, "bgd": (50 - 18 - 10) + 18,
                 "policy": 10, "eval": 20}
    assert sum(t.values()) == 100            # the parts add up to the busy
    assert S.stage_times(ops, 0, 100, by_op="bgd") == {
        "conditional.2": 50 - 18 - 10, "fusion.3": 18}


def test_unscoped_takes_what_no_scope_covers_and_window_clips():
    ops = {DEV: [("unscoped", 0, 20, "copy.1"), ("gather", 20, 30, "g"),
                 ("unscoped", 30, 50, "copy.2")],
           "/device:TPU:1": [("aggregate", 10, 40, "a")]}
    t = S.stage_times(ops, 10, 45)
    # device 0: unscoped 10 + 15, gather 10; device 1: aggregate 30
    assert t == {"unscoped": 25 / 2, "gather": 10 / 2, "aggregate": 30 / 2}


def test_idle_holes_split_to_the_innermost_span():
    spans = [("run_scanned", 0, 100), ("mfl.call", 2, 98),
             ("mfl.draw_xs", 3, 10), ("mfl.dispatch", 10, 20),
             ("mfl.fetch", 20, 80), ("mfl.decode", 80, 90),
             ("mfl.export", 90, 97)]
    ops = {DEV: [("bgd", 15, 70, "x")]}
    idle = S.idle_by_span(ops, spans, 0, 100)
    assert idle == {"run_scanned": 2 + 2, "mfl.call": 1 + 1,
                    "mfl.draw_xs": 7, "mfl.dispatch": 5, "mfl.fetch": 10,
                    "mfl.decode": 10, "mfl.export": 7}
    assert sum(idle.values()) == 100 - 55
    assert S.idle_by_span(ops, [("run_scanned", 0, 10)], 0, 20) == {
        "run_scanned": 10, "none": 5}


def _spans(calls, window):
    return [("run_scanned", 1000 + i * window / calls,
             1000 + (i + 1) * window / calls) for i in range(calls)]


def test_reduce_scopes_checks_the_trace_is_the_runs():
    spans = _spans(2, 2e9) + [("mfl.fetch", 1000, 1000 + 5e8)]
    ops = {DEV: [("bgd", 1000 + 5e8, 1000 + 2e9, "x")]}
    r = S.reduce_scopes(ops, spans, calls=2, window_s=2.0)
    assert r["stages_ms"] == {"bgd": pytest.approx(1500.0)}
    assert r["busy_ms"] == pytest.approx(1500.0)
    assert r["idle_ms"] == {"mfl.fetch": pytest.approx(500.0)}
    with pytest.raises(ValueError, match="not this run's"):
        S.reduce_scopes(ops, spans, calls=3, window_s=2.0)
    with pytest.raises(ValueError, match="not this run's"):
        S.reduce_scopes(ops, spans, calls=2, window_s=2.0 + 2e-6)
    S.reduce_scopes(ops, spans, calls=2, window_s=2.0 + 5e-7)
    with pytest.raises(ValueError):
        S.reduce_scopes(ops, [], calls=0, window_s=0.0)


def _xspace(path_in, with_spans=True):
    """An XSpace with one device plane of three ops and a host plane with
    the harness's call span and, optionally, the program's spans.
    ``path_in`` says where the op path sits: the event's stats, its
    metadata's stats, or the HLO text of its name."""
    space = S._xspace_class()()
    dev = space.planes.add(name=DEV)
    sm = dev.stat_metadata.add(key=7)
    sm.value.id, sm.value.name = 7, "tf_op"
    ref = dev.stat_metadata.add(key=8)
    ref.value.id, ref.value.name = 8, "jit(f)/while/body/round/policy/mul"
    line = dev.lines.add(name="XLA Ops", timestamp_ns=1000)
    names = {1: ("fusion.1", "jit(f)/while/body/round/bgd/dot"),
             2: ("fusion.2", "jit(f)/while/body/round/policy/mul"),
             3: ("while.3", "jit(f)/while")}
    for key, (name, path) in names.items():
        md = dev.event_metadata.add(key=key).value
        md.id = key
        md.name = (f'%{name} = f32[2] fusion(%p), metadata={{op_name='
                   f'"{path}"}}' if path_in == "hlo_text" else name)
        if path_in == "metadata_stats":
            st = md.stats.add(metadata_id=7)
            if key == 2:
                st.ref_value = 8
            else:
                st.str_value = path
    for key, off, dur in ((3, 0, 100), (1, 10, 40), (2, 60, 20)):
        ev = line.events.add(metadata_id=key, offset_ps=off * 1000,
                             duration_ps=dur * 1000)
        if path_in == "event_stats":
            ev.stats.add(metadata_id=7, str_value=names[key][1])
    host = space.planes.add(name="/host:CPU")
    hline = host.lines.add(name="python", timestamp_ns=990)
    spans = [("run_scanned", 0, 120)]
    if with_spans:
        spans += [("mfl.call", 1, 117), ("mfl.fetch", 50, 65)]
    for i, (name, off, dur) in enumerate(spans, start=10):
        md = host.event_metadata.add(key=i).value
        md.id, md.name = i, name
        hline.events.add(metadata_id=i, offset_ps=off * 1000,
                         duration_ps=dur * 1000)
    return space


@pytest.mark.parametrize("path_in",
                         ["event_stats", "metadata_stats", "hlo_text"])
def test_read_finds_the_op_path_wherever_the_trace_keeps_it(tmp_path,
                                                            path_in):
    f = tmp_path / "t.xplane.pb"
    f.write_bytes(_xspace(path_in).SerializeToString())
    got = S.read(str(f))
    assert got["sources"] == {path_in: 3}
    ops = got["device_ops"][DEV]
    assert [o[0] for o in ops] == ["unscoped", "bgd", "policy"]
    assert ops[0][1:3] == (1000.0, 1100.0)
    assert [o[3] for o in ops] == ["while.3", "fusion.1", "fusion.2"]
    assert got["spans"] == [("run_scanned", 990.0, 1110.0),
                            ("mfl.call", 991.0, 1108.0),
                            ("mfl.fetch", 1040.0, 1105.0)]


def _run(calls=1, window_s=120e-9, rounds=2):
    return {"trace": {"calls": calls, "window_s": window_s,
                      "rounds": rounds}}


def test_metrics_from_the_newest_trace_of_the_config(tmp_path):
    cfg = {"name": "toy"}
    d = tmp_path / "toy.cell" / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    (d / "h.xplane.pb").write_bytes(
        _xspace("metadata_stats").SerializeToString())
    r = S.for_run(_run(), cfg, out_root=tmp_path)
    # window [990, 1110]: busy 1000-1100; idle 990-991 under run_scanned,
    # 991-1000 under mfl.call, 1100-1105 under fetch, 1105-1108 under
    # mfl.call, 1108-1110 under run_scanned
    assert r["stages_ms"] == pytest.approx(
        {"unscoped": 40e-6, "bgd": 40e-6, "policy": 20e-6})
    assert r["busy_ms"] == pytest.approx(100e-6)
    assert r["idle_ms"] == pytest.approx(
        {"run_scanned": 3e-6, "mfl.call": 12e-6, "mfl.fetch": 5e-6})
    with pytest.raises(ValueError, match="not this run's"):
        S.for_run(_run(calls=2), cfg, out_root=tmp_path)
    assert S.for_run({"trace": None}, cfg, out_root=tmp_path) is None
    with pytest.raises(FileNotFoundError):
        S.for_run(_run(), {"name": "other"}, out_root=tmp_path)


def test_a_program_without_scopes_or_spans_reports_nothing(tmp_path,
                                                          monkeypatch):
    f = tmp_path / "old.cell" / "t.xplane.pb"
    f.parent.mkdir()
    space = _xspace("none", with_spans=False)
    f.write_bytes(space.SerializeToString())
    monkeypatch.setattr(S, "BENCH", tmp_path)
    got = S.read(str(f))
    assert got["sources"] == {"none": 3}
    r = S.for_run(_run(), {"name": "old"}, out_root=tmp_path)
    assert set(r["stages_ms"]) == {"unscoped"}
    assert set(r["idle_ms"]) == {"run_scanned"}


def test_summarise_takes_its_window_from_the_program_spans(tmp_path):
    f = tmp_path / "t.xplane.pb"
    f.write_bytes(_xspace("event_stats").SerializeToString())
    r = S.summarise(f)
    # window [991, 1108]: idle 991-1000 and 1105-1108 under mfl.call
    assert r["calls"] == 1
    assert r["busy_ms"] == pytest.approx(100e-6)
    assert r["idle_ms"] == pytest.approx({"mfl.call": 12e-6,
                                          "mfl.fetch": 5e-6})
