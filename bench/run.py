#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is found by name: its entry in ``BENCHMARK.json``
names a configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); the mix names the driver module that runs
the system under test (``bench/drivers/<driver>.py``); each per-layer metric
is a reducer of its own (``bench/metrics/<metric>.py``); the limits of the
correctness check are ``bench/limits/<cell>.json``.

With ``--trace 0`` the line carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of a few
steady calls.  Either way the run then checks what the timed path produced
against the plain reference (``bench/reference``) and prints each number
compared beside its limit, last on standard error and last in the line.

Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result.
"""
from __future__ import annotations

import time

T_START = time.time()           # set-up is timed from here

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def load_module(path: Path):
    """Import a file of the benchmark by its path (names may hold '-')."""
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}".replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, root: Path = ROOT):
    """(benchmark spec, cell entry, configuration, traffic mix)."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cfg = json.loads((root / conf["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
    return spec, cell, cfg, traffic


def device_info() -> dict:
    import jax
    devs = jax.devices()
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def execute(spec, cell, cfg, traffic, seed: int, seconds: float,
            trace: bool, out_dir: Path, t_start: float = T_START) -> dict:
    """Drive the cell, check it, and return the result line's object.  No
    look for a chip: ``main`` makes it."""
    from bench import check

    driver = load_module(BENCH / "drivers" / f"{traffic['driver']}.py")
    run = driver.run(cfg, traffic, seed=seed, seconds=seconds, trace=trace,
                     out_dir=out_dir, t_start=t_start)
    device = device_info()          # the peak, before the reference runs
    run.pop("release")()            # frees the program's state
    run["seed"] = seed
    verdict = check.check(cell["name"], cfg, traffic, seed, run["record"])

    if trace:
        metrics = {}
        for m in spec["per_layer"]:
            if cell["name"] not in m.get("workloads", [cell["name"]]):
                continue
            reducer = load_module(BENCH / "metrics" / f"{m['name']}.py")
            value = reducer.reduce(run, cfg, device)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = run["trace"]["busy_s"]
        device["window_s"] = run["trace"]["window_s"]
    else:
        metrics = {}
        for m in spec["end_to_end"]:
            if cell["name"] in m.get("workloads", [cell["name"]]):
                metrics[m["name"]] = {"value": run["e2e"][m["name"]],
                                      "unit": m["unit"]}
    out = {"correct": verdict["correct"] and run["failed"] == 0,
           "attempted": run["attempted"], "failed": run["failed"],
           "metrics": metrics, "device": device}
    if trace:
        out["breakdown"] = run["trace"]["breakdown"]
    out["check"] = {k: {"value": _num(v["value"]), "limit": v["limit"]}
                    for k, v in verdict["numbers"].items()}
    return out


def _num(x):
    """A reading as JSON can hold it: non-finite readings become text."""
    x = float(x)
    return x if math.isfinite(x) else str(x)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec, cell, cfg, traffic = load_cell(args.workload)
    from bench import cache
    # the cell's own cache, which the program takes from the environment
    cache_dir = cache.cache_dir(ROOT, cell["name"])
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)

    try:
        import jax
        devs = jax.devices()
    except Exception as e:                     # no backend at all
        print(f"bench: JAX found no accelerator: {e}", file=sys.stderr)
        return 2
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        print(f"bench: cell {cell['name']} needs {cell['chips']} TPU chip(s);"
              f" JAX found {len(devs)} {devs[0].platform} device(s)",
              file=sys.stderr)
        return 2
    if not (ROOT / "src" / "repro").is_dir():
        print("bench: the system under test (src/repro) is not in this "
              "checkout", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    jax.config.update("jax_compilation_cache_dir", str(cache_dir))
    jax.config.update("jax_compilation_cache_max_size", cache.MAX_BYTES)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    out = execute(spec, cell, cfg, traffic, args.seed, args.seconds,
                  bool(args.trace), ROOT / "bench" / "out" / cell["name"])
    gone = cache.prune(cache_dir, int(T_START * 1e9))
    print(f"bench: removed {len(gone)} compile-cache entries this run did "
          f"not use", file=sys.stderr)
    for name, v in out["check"].items():
        print(f"check {name} = {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out, allow_nan=False), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
