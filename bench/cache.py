"""One cell's persistent compilation cache, in the same state for every run.

Each cell keeps JAX's cache at a fixed path inside the checkout,
``.jax_cache/bench/<cell>``, which the program takes through
``JAX_COMPILATION_CACHE_DIR``.  With a size limit set, JAX writes beside
each entry the time it was last used (``<key>-atime``).  At the end of a
run ``prune`` removes every entry the run did not use:

* a program whose cache key is the same for every seed is used by every run
  and stays, so only the first run of the cell compiles it;
* a program whose key changes with the seed (the fused round program bakes
  seed-drawn constants into its code, PERF.md section 7) keeps only the
  entry of the last run, so a run compiles it unless the run before had the
  same seed.

A seed that comes back later in a check thus sets up as it did the first
time, and ``setup_s`` measures the program, not which seeds ran before.
"""
from __future__ import annotations

from pathlib import Path
from typing import List

#: file suffixes of an entry and of its last use (``jax._src.lru_cache``)
CACHE_SUFFIX = "-cache"
ATIME_SUFFIX = "-atime"
#: the size limit that makes JAX keep ``-atime`` files; far above what a
#: cell's programs take
MAX_BYTES = 8 << 30


def cache_dir(root: Path, cell: str) -> Path:
    return root / ".jax_cache" / "bench" / cell


def prune(path: Path, since_ns: int) -> List[str]:
    """Remove the entries under ``path`` last used before ``since_ns``
    (``time.time_ns()``) and return their keys."""
    gone = []
    for entry in sorted(path.glob(f"*{CACHE_SUFFIX}")):
        key = entry.name[:-len(CACHE_SUFFIX)]
        atime = path / f"{key}{ATIME_SUFFIX}"
        used = (int.from_bytes(atime.read_bytes(), "little")
                if atime.exists() else 0)
        if used < since_ns:
            entry.unlink()
            atime.unlink(missing_ok=True)
            gone.append(key)
    return gone
