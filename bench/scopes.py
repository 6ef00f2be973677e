"""Device time per stage of the round program and device idle time per host
phase, from the traced run's ``.xplane.pb``.

The program names its parts (``src/repro/fl``):

* stage scopes on the ops of the round program: ``round/policy``,
  ``round/gather``, ``round/bgd``, ``round/aggregate``, ``round/eval``
  (``jax.named_scope``, in each op's ``op_name`` path);
* host spans per call of ``run_scanned``: ``mfl.call`` around
  ``mfl.draw_xs``, ``mfl.dispatch``, ``mfl.fetch``, ``mfl.decode`` and
  ``mfl.export`` (``jax.profiler.TraceAnnotation``, tagged ``round0``).

Over the traced window (the first harness ``run_scanned`` span's start to the
last one's end, as ``bench/trace.py`` sets it) this gives, averaged over the
device planes:

* self time per stage: each op's duration less the ops nested in it (a
  loop's or a ``conditional``'s body) goes to the stage in its op path, or to
  ``unscoped``; the parts add up to the busy time;
* idle time per span: each hole in the busy union is split at the host spans'
  edges, and each piece goes to the innermost span that covers it (``none``
  where no span does).

The op path is read from whichever place the trace keeps it: the event's own
stats, its metadata's stats (the trace viewer's ``tf_op``), or the HLO text
in the op's name (``metadata={op_name=...}``).  ``read`` counts which.

A program without the scopes or spans (one older than them) yields no
stage or no phase time, and the metrics that read them report nothing.
"""
from __future__ import annotations

import bisect
import functools
import re
import sys
import time
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

from bench.trace import (CALL_SPAN, HARNESS_SPANS, find_xplane, gaps,
                         merge, op_name, self_times)

BENCH = Path(__file__).resolve().parent
STAGES = ("policy", "gather", "bgd", "aggregate", "eval")
UNSCOPED = "unscoped"
PHASES = ("mfl.call", "mfl.draw_xs", "mfl.dispatch", "mfl.fetch",
          "mfl.decode", "mfl.export")
_STAGE = re.compile(r"(?:^|/)round/(" + "|".join(STAGES) + r")(?:/|$)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')

Event = Tuple[str, float, float]             # (label, start ns, end ns)
# device ops are (stage, start ns, end ns[, XLA name])


def stage_of(path: str) -> str:
    """The stage named in an op path, or ``unscoped``."""
    m = _STAGE.search(path)
    return m.group(1) if m else UNSCOPED


# ---------------------------------------------------------------------------
# reduction, on events already read (the tests feed these by hand)
# ---------------------------------------------------------------------------
def stage_times(device_ops: Dict[str, list], lo: float, hi: float,
                by_op: Optional[str] = None) -> Dict[str, float]:
    """Self time (ns) per stage inside [lo, hi], averaged over devices, or,
    with ``by_op``, per XLA op name of the ops of that stage."""
    out: Dict[str, float] = defaultdict(float)
    for ops in device_ops.values():
        inside = [(n if by_op is None else f"{n}|{x[0] if x else ''}",
                   max(s, lo), min(e, hi)) for n, s, e, *x in ops
                  if min(e, hi) > max(s, lo)]
        for n, t in self_times(inside).items():
            if by_op is None:
                out[n] += t / len(device_ops)
            elif n.startswith(by_op + "|"):
                out[n.split("|", 1)[1]] += t / len(device_ops)
    return dict(out)


def idle_by_span(device_ops: Dict[str, List[Event]], spans: Sequence[Event],
                 lo: float, hi: float) -> Dict[str, float]:
    """Device idle time (ns) inside [lo, hi] per innermost covering span,
    averaged over devices."""
    # the span edges cut [lo, hi] into pieces, each under one innermost span
    cuts = sorted({lo, hi} | {t for _, a, b in spans for t in (a, b)
                              if lo < t < hi})
    label = []
    for a, b in zip(cuts, cuts[1:]):
        inner = [(bb - aa, n) for n, aa, bb in spans if aa <= a and b <= bb]
        label.append(min(inner)[1] if inner else "none")
    out: Dict[str, float] = defaultdict(float)
    for ops in device_ops.values():
        union = merge([(s, e) for _, s, e, *_ in ops])
        for s, e in gaps(union, lo, hi):
            i = bisect.bisect_right(cuts, s) - 1
            while s < e:
                t = min(e, cuts[i + 1])
                out[label[i]] += (t - s) / len(device_ops)
                s, i = t, i + 1
    return dict(out)


def window(spans: Sequence[Event], call_span: str = CALL_SPAN
           ) -> Tuple[float, float, int]:
    """(start, end, number of calls) of the call spans."""
    calls = sorted((s, e) for n, s, e in spans if n == call_span)
    if not calls:
        raise ValueError(f"trace holds no {call_span!r} span")
    return calls[0][0], calls[-1][1], len(calls)


def check_identity(spans: Sequence[Event], calls: int, window_s: float,
                   call_span: str = CALL_SPAN) -> Tuple[float, float]:
    """The window, after checking that these spans are the run's own: as
    many call spans as the run made, over the same window to 1 us."""
    lo, hi, n = window(spans, call_span)
    if n != calls or abs((hi - lo) / 1e9 - window_s) > 1e-6:
        raise ValueError(
            f"trace is not this run's: {n} call spans over "
            f"{(hi - lo) / 1e9:.9f} s, the run made {calls} calls over "
            f"{window_s:.9f} s")
    return lo, hi


def reduce_scopes(device_ops: Dict[str, List[Event]], spans: Sequence[Event],
                  calls: int, window_s: float, call_span: str = CALL_SPAN
                  ) -> dict:
    """Stage self times, per-span idle times and the busy time they split
    (all in ms) of a traced run that made ``calls`` calls, each inside a
    ``call_span`` span, over ``window_s``."""
    lo, hi = check_identity(spans, calls, window_s, call_span)
    stages = stage_times(device_ops, lo, hi)
    idle = idle_by_span(device_ops, spans, lo, hi)
    busy = (hi - lo) - sum(idle.values())       # the holes are the idle
    return {"stages_ms": {k: v / 1e6 for k, v in stages.items()},
            "idle_ms": {k: v / 1e6 for k, v in idle.items()},
            "busy_ms": busy / 1e6, "calls": calls}


# ---------------------------------------------------------------------------
# reading an .xplane.pb
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=1)
def _xspace_class():
    """The ``XSpace`` message class, built from its field numbers
    (``tsl/profiler/protobuf/xplane.proto``) so that no TensorFlow is
    imported; map fields are read as their wire-equal repeated entries."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory
    F = descriptor_pb2.FieldDescriptorProto
    fdp = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="bench_xplane", syntax="proto3")

    def msg(name, *fields):
        m = fdp.message_type.add(name=name)
        for fname, num, typ, label, tname in fields:
            f = m.field.add(name=fname, number=num, type=typ, label=label)
            if tname:
                f.type_name = ".bench_xplane." + tname
    opt, rep = F.LABEL_OPTIONAL, F.LABEL_REPEATED
    msg("XStat", ("metadata_id", 1, F.TYPE_INT64, opt, None),
        ("double_value", 2, F.TYPE_DOUBLE, opt, None),
        ("uint64_value", 3, F.TYPE_UINT64, opt, None),
        ("int64_value", 4, F.TYPE_INT64, opt, None),
        ("str_value", 5, F.TYPE_STRING, opt, None),
        ("bytes_value", 6, F.TYPE_BYTES, opt, None),
        ("ref_value", 7, F.TYPE_UINT64, opt, None))
    msg("XEvent", ("metadata_id", 1, F.TYPE_INT64, opt, None),
        ("offset_ps", 2, F.TYPE_INT64, opt, None),
        ("duration_ps", 3, F.TYPE_INT64, opt, None),
        ("stats", 4, F.TYPE_MESSAGE, rep, "XStat"))
    msg("XLine", ("id", 1, F.TYPE_INT64, opt, None),
        ("name", 2, F.TYPE_STRING, opt, None),
        ("timestamp_ns", 3, F.TYPE_INT64, opt, None),
        ("events", 4, F.TYPE_MESSAGE, rep, "XEvent"))
    msg("XEventMetadata", ("id", 1, F.TYPE_INT64, opt, None),
        ("name", 2, F.TYPE_STRING, opt, None),
        ("display_name", 4, F.TYPE_STRING, opt, None),
        ("stats", 5, F.TYPE_MESSAGE, rep, "XStat"))
    msg("XStatMetadata", ("id", 1, F.TYPE_INT64, opt, None),
        ("name", 2, F.TYPE_STRING, opt, None))
    msg("EventMetadataEntry", ("key", 1, F.TYPE_INT64, opt, None),
        ("value", 2, F.TYPE_MESSAGE, opt, "XEventMetadata"))
    msg("StatMetadataEntry", ("key", 1, F.TYPE_INT64, opt, None),
        ("value", 2, F.TYPE_MESSAGE, opt, "XStatMetadata"))
    msg("XPlane", ("id", 1, F.TYPE_INT64, opt, None),
        ("name", 2, F.TYPE_STRING, opt, None),
        ("lines", 3, F.TYPE_MESSAGE, rep, "XLine"),
        ("event_metadata", 4, F.TYPE_MESSAGE, rep, "EventMetadataEntry"),
        ("stat_metadata", 5, F.TYPE_MESSAGE, rep, "StatMetadataEntry"))
    msg("XSpace", ("planes", 1, F.TYPE_MESSAGE, rep, "XPlane"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def _stat_strings(stats, stat_names) -> List[Tuple[str, str]]:
    """(stat name, text) of the stats that hold text."""
    out = []
    for st in stats:
        if st.str_value:
            text = st.str_value
        elif st.ref_value:
            text = stat_names.get(st.ref_value, "")
        else:
            continue
        out.append((stat_names.get(st.metadata_id, ""), text))
    return out


def _op_path(ev, md, stat_names) -> Tuple[str, str]:
    """(op path, where it was found) of a device op; ('', 'none') where
    the trace holds none."""
    for where, stats in (("event_stats", ev.stats),
                         ("metadata_stats", md.stats if md else ())):
        for _, text in _stat_strings(stats, stat_names):
            if _STAGE.search(text):
                return text, where
    for text in ((md.name, md.display_name) if md else ()):
        m = _OP_NAME.search(text)
        if m:
            return m.group(1), "hlo_text"
    for where, stats in (("event_stats", ev.stats),
                         ("metadata_stats", md.stats if md else ())):
        for name, text in _stat_strings(stats, stat_names):
            if name == "tf_op":
                return text, where
    return "", "none"


@functools.lru_cache(maxsize=2)
def read(path: str) -> dict:
    """Device ops as ``(stage, start, end, XLA name)`` per device plane,
    host spans (``mfl.*`` and the harness's own) as ``(name, start, end)``,
    and how many op paths came from which place."""
    space = _xspace_class()()
    space.ParseFromString(Path(path).read_bytes())
    device_ops: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    sources: Dict[str, int] = defaultdict(int)
    wanted = set(PHASES) | set(HARNESS_SPANS)
    for plane in space.planes:
        meta = {e.key: e.value for e in plane.event_metadata}
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                # an op's path is that of its HLO instruction, which is
                # its metadata entry: found once per entry, at its first
                # event
                by_meta: Dict[int, tuple] = {}
                ops = []
                t0 = line.timestamp_ns
                for ev in line.events:
                    found = by_meta.get(ev.metadata_id)
                    if found is None:
                        md = meta.get(ev.metadata_id)
                        path, where = _op_path(ev, md, stat_names)
                        name = op_name(md.name) if md is not None else ""
                        found = by_meta[ev.metadata_id] = (
                            stage_of(path), where, name)
                    sources[found[1]] += 1
                    s = t0 + ev.offset_ps / 1e3
                    ops.append((found[0], s, s + ev.duration_ps / 1e3,
                                found[2]))
                device_ops[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                t0 = line.timestamp_ns
                for ev in line.events:
                    md = meta.get(ev.metadata_id)
                    if md is None or md.name not in wanted:
                        continue
                    s = t0 + ev.offset_ps / 1e3
                    spans.append((md.name, s, s + ev.duration_ps / 1e3))
    return {"device_ops": device_ops, "spans": spans,
            "sources": dict(sources)}


def summarise(path, call_span: str = "mfl.call") -> dict:
    """Stage and span times of any trace of the program, over the window
    its ``call_span`` spans cover: for a trace taken outside the
    benchmark, whose harness spans it lacks."""
    got = read(str(path))
    lo, hi, n = window(got["spans"], call_span)
    return reduce_scopes(got["device_ops"], got["spans"], n, (hi - lo) / 1e9,
                         call_span)


# ---------------------------------------------------------------------------
# what the metric files call
# ---------------------------------------------------------------------------
def trace_file(cfg: dict, out_root: Path = BENCH / "out") -> Path:
    """The newest ``.xplane.pb`` of the configuration's cells."""
    found = [find_xplane(d) for d in Path(out_root).glob(f"{cfg['name']}.*")
             if d.is_dir() and any(d.glob("**/*.xplane.pb"))]
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {out_root}/"
                                f"{cfg['name']}.*")
    return max(found, key=lambda p: p.stat().st_mtime)


def for_run(run: dict, cfg: dict, out_root: Path = BENCH / "out"
            ) -> Optional[dict]:
    """The traced run's stage and span times, or None for an untraced
    run.  Raises if the newest trace is not this run's."""
    t = run.get("trace")
    if not t:
        return None
    return _reduced(str(trace_file(cfg, out_root)), t["calls"], t["window_s"])


@functools.lru_cache(maxsize=2)
def _reduced(path: str, calls: int, window_s: float) -> dict:
    t0 = time.perf_counter()
    got = read(path)
    t1 = time.perf_counter()
    out = reduce_scopes(got["device_ops"], got["spans"], calls, window_s)
    print(f"scopes: {Path(path).stat().st_size} bytes read in "
          f"{t1 - t0:.1f} s, reduced in {time.perf_counter() - t1:.1f} s; "
          f"op paths from {got['sources']}", file=sys.stderr, flush=True)
    return out


def stage_ms_per_round(run: dict, cfg: dict, stage: str) -> Optional[float]:
    """Device self time under ``round/<stage>`` per traced round, in ms;
    None where the program names no stage."""
    r = for_run(run, cfg)
    if r is None or not set(r["stages_ms"]) & set(STAGES):
        return None
    return r["stages_ms"].get(stage, 0.0) / run["trace"]["rounds"]


def idle_ms_per_call(run: dict, cfg: dict, phases: Sequence[str]
                     ) -> Optional[float]:
    """Device idle time under the host spans ``phases`` per traced call, in
    ms; None where the program leaves no ``mfl.*`` span."""
    r = for_run(run, cfg)
    if r is None or not set(r["idle_ms"]) & set(PHASES):
        return None
    return sum(r["idle_ms"].get(p, 0.0) for p in phases) / r["calls"]
