#!/usr/bin/env python3
"""Readings of the correctness check's numbers for stand-ins of the
program, to set the limits from (``bench/limits/<cell>.json``).

    python bench/readings.py --workload <cell> --seeds 1 2 3 \
        --what control unchanged half_batch altered [--out file.jsonl]

Each stand-in is the plain reference put in the program's place:

* ``control``: the reference computed one precision step lower than the
  configuration states: its float32 matmuls and convolutions at ``high``
  (three bfloat16 passes) for ``highest``, its solver in float32;
* ``unchanged``: a step that returns its state unchanged;
* ``half_batch``: each client's step on the first half of its samples only,
  the mean taken over those;
* ``altered``: client 0's bit of every round's schedule flipped where the
  decision is made.

Each prints one JSON line ``{"cell", "seed", "what", "readings"}``.  The
benchmark's own runs never call this; it needs a TPU (the tests call
``readings`` directly, on the CPU, at a tiny size).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
if str(BENCH.parent) not in sys.path:
    sys.path.insert(0, str(BENCH.parent))

from bench.reference import fl  # noqa: E402


LOWER = {"highest": "high", "high": "default"}


def stand_in(cfg, traffic, seed: int, what: str):
    import numpy as np
    R = traffic["rounds_per_call"]
    if what == "control":
        rec, _ = fl.walk(cfg, traffic, seed, R,
                         precision=LOWER[cfg["precision"]["matmul"]],
                         np_dtype=np.float32)
    else:
        rec, _ = fl.walk(cfg, traffic, seed, R, fault=what)
    return rec


def readings(cfg, traffic, seed: int, what: str) -> dict:
    rec = stand_in(cfg, traffic, seed, what)
    _, rd = fl.walk(cfg, traffic, seed, len(rec.a), prog=rec)
    return rd


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--what", nargs="+", required=True,
                    choices=("control",) + fl.FAULTS)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    from bench.run import load_cell
    _, cell, cfg, traffic = load_cell(args.workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("readings: no TPU", file=sys.stderr)
        return 2
    out = open(args.out, "a") if args.out else None
    for seed in args.seeds:
        for what in args.what:
            t0 = time.perf_counter()
            rd = readings(cfg, traffic, seed, what)
            line = json.dumps({"cell": cell["name"], "seed": seed,
                               "what": what, "readings": rd,
                               "seconds": time.perf_counter() - t0})
            print(line, flush=True)
            if out:
                out.write(line + "\n")
                out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
