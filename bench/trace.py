"""From a profiler trace to the per-layer numbers of a traced run.

A trace is reduced to two event lists on one clock, in nanoseconds:

* device ops: ``(name, start, end)`` of every op on each device plane's
  "XLA Ops" line;
* harness spans: ``(name, start, end)`` of the benchmark's own
  ``TraceAnnotation`` spans on the host planes (``run_scanned`` around each
  call of the entry the window drives, ``harness`` around the benchmark's
  bookkeeping between calls).

From those, with the traced window taken as the first call span's start to
the last one's end:

* busy: the length of the union of the device op intervals inside the
  window, averaged over the devices; idle share is 1 - busy / window;
* host time per call: each call span's length minus the busy time inside it;
* top ops: the device ops with the most self time (their time minus that
  of the ops nested inside them, such as a loop's body), by XLA name;
* idle gaps: the holes in the busy union, each labelled by the harness span
  that covers its midpoint (``none`` where no span does).
"""
from __future__ import annotations

from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

Interval = Tuple[float, float]
Event = Tuple[str, float, float]

CALL_SPAN = "run_scanned"
HARNESS_SPANS = (CALL_SPAN, "harness")


def merge(intervals: Sequence[Interval]) -> List[Interval]:
    """Sorted, disjoint union of half-open intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def covered(union: Sequence[Interval], lo: float, hi: float) -> float:
    """Length of ``union`` (disjoint) inside [lo, hi]."""
    return sum(e - s for s, e in clip(union, lo, hi))


def gaps(union: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The holes of ``union`` (disjoint, sorted) inside [lo, hi]."""
    out, t = [], lo
    for s, e in clip(union, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def self_times(ops: Sequence[Event]) -> Dict[str, float]:
    """Total self time per op name: each op's duration minus the durations
    of the ops directly nested inside it (contained in its interval)."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[Tuple[str, float, float]] = []
    for n, s, e in sorted(ops, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][2] <= s:
            stack.pop()
        if stack and e <= stack[-1][2]:
            out[stack[-1][0]] -= e - s
        out[n] += e - s
        stack.append((n, s, e))
    return out


def reduce_events(device_ops: Dict[str, List[Event]],
                  spans: List[Event], rounds: int, top: int = 10) -> dict:
    """The traced run's numbers from device ops per device and harness
    spans (all on one clock, in ns)."""
    calls = sorted((s, e) for n, s, e in spans if n == CALL_SPAN)
    if not calls or not device_ops:
        raise ValueError("trace holds no call span or no device op")
    lo, hi = calls[0][0], calls[-1][1]
    window = hi - lo
    unions = {d: merge([(s, e) for _, s, e in ops])
              for d, ops in device_ops.items()}
    busy = sum(covered(u, lo, hi) for u in unions.values()) / len(unions)
    host_ms = [((e - s) - sum(covered(u, s, e) for u in unions.values())
                / len(unions)) / 1e6 for s, e in calls]

    per_op: Dict[str, float] = defaultdict(float)
    for ops in device_ops.values():
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in ops
                  if min(e, hi) > max(s, lo)]
        for n, t in self_times(inside).items():
            per_op[n] += t / len(device_ops)
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]

    labelled = []
    first = next(iter(sorted(unions)))
    for s, e in gaps(unions[first], lo, hi):
        mid = 0.5 * (s + e)
        inner = [(ss, ee, n) for n, ss, ee in spans if ss <= mid < ee]
        label = min(inner, key=lambda x: x[1] - x[0])[2] if inner else "none"
        labelled.append((label, (e - s) / 1e9))
    labelled.sort(key=lambda x: -x[1])
    return {"window_s": window / 1e9, "busy_s": busy / 1e9,
            "idle_share": 1.0 - busy / window, "rounds": rounds,
            "host_ms_per_call": sum(host_ms) / len(host_ms),
            "calls": len(calls),
            "breakdown": {
                "device_ops": [[n, t / 1e9] for n, t in top_ops],
                "idle_gaps": [[n, t] for n, t in labelled[:top]]}}


def op_name(hlo: str) -> str:
    """The op's XLA name from the trace's event name, which on a TPU is the
    whole HLO instruction (``%fusion.12 = f32[...] fusion(...)``)."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def profile_options():
    """Device ops and the harness's spans; no Python call tracing and no
    HLO protos, which would make the trace several times larger."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    return opts


def read_xplane(path: Path, span_names: Sequence[str] = HARNESS_SPANS
                ) -> Tuple[Dict[str, List[Event]], List[Event]]:
    """(device ops per device plane, harness spans) from an ``.xplane.pb``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    device_ops: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    wanted = set(span_names)
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    names: Dict[str, str] = {}
                    ops = []
                    for ev in line.events:
                        n = ev.name
                        short = names.get(n)
                        if short is None:
                            short = names[n] = op_name(n)
                        s = ev.start_ns
                        ops.append((short, s, s + ev.duration_ns))
                    device_ops[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in wanted:
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
    return device_ops, spans


def find_xplane(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).glob("**/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]

