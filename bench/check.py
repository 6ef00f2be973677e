"""Whether what the timed path produced is correct.

The run's first call (its first R rounds, through the window's own entry
and compiled program) is followed by the plain reference
(``bench/reference/fl.py``) from the same seed, and each number below is
held to the limit in ``bench/limits/<cell>.json``:

* ``sched_excess``: how much worse the program's schedule scores
  under the reference's objective than the reference's own search result,
  over |J(empty schedule)|, worst of the first three rounds;
* ``J_gap``: the program's reported objective against the
  reference's objective of the same schedule, same scale, worst round;
  ``J_gap_first`` the same in the round after the first one with uploads,
  whose objective reads the queues and trackers those uploads left;
* ``part_mismatch``: rounds whose participants (scheduled and within
  tau_max) differ;
* ``weights_gap``: largest difference of an Eq. 12 weight;
* ``energy_gap``: cumulative energy, relative (to E_add while it is
  smaller), worst round;
* ``loss_gap`` / ``acc_gap``: held-out fused cross-entropy (relative) and
  accuracies (absolute), worst round; ``loss_gap_first`` the cross-entropy
  of the globals after the first round with uploads;
* ``dparam_gap``: per leaf, the gap between the program's and the
  reference's norm of the change over the call, over the larger of the
  reference's norm of that leaf and of the median leaf, worst leaf; leaves
  the reference moves by less than a thousandth of the median leaf's are
  left out;
* ``queue_gap``: Lyapunov queues after the call, over max(max Q, E_add);
* ``tracker_gap``: zeta (relative) and delta (over the largest delta of the
  modality) after the call, worst entry.

A number with no limit in the cell's file is printed but not compared.
"""
from __future__ import annotations

import importlib
import json
import sys
from pathlib import Path

LIMITS = Path(__file__).resolve().parent / "limits"


def limits_for(cell: str) -> dict:
    return json.loads((LIMITS / f"{cell}.json").read_text())["limits"]


def reference(traffic: dict):
    """The reference module a mix names (``bench/reference/<name>.py``,
    default ``fl``); it provides ``walk(cfg, traffic, seed, rounds,
    prog=record)``."""
    return importlib.import_module(
        f"bench.reference.{traffic.get('reference', 'fl')}")


def check(cell: str, cfg: dict, traffic: dict, seed: int, record) -> dict:
    limits = limits_for(cell)
    _, readings = reference(traffic).walk(cfg, traffic, seed, len(record.a),
                                          prog=record)
    print("readings " + json.dumps(readings), file=sys.stderr, flush=True)
    numbers = {k: {"value": readings[k], "limit": lim}
               for k, lim in limits.items()}
    correct = all(v["value"] <= v["limit"] for v in numbers.values())
    return {"correct": correct, "numbers": numbers}
