"""A run leaves in its cell's compile cache only what it used, so that a
program keyed by the seed compiles in every run and one that is not
compiles once."""
import os
import subprocess
import sys
import textwrap
import time

from bench import cache

# one process: compile a program with a constant baked in, through the
# cache at JAX_COMPILATION_CACHE_DIR, and print the cache hits and misses it saw
PROGRAM = textwrap.dedent("""
    import sys
    import jax, jax.numpy as jnp, numpy as np
    from bench import cache
    jax.config.update("jax_compilation_cache_max_size", cache.MAX_BYTES)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    seen = []
    jax.monitoring.register_event_listener(lambda name, **_: seen.append(name))
    c = float(sys.argv[1])
    jax.jit(lambda x: jnp.sin(x) * c + jnp.cos(x))(np.ones(8, np.float32))
    print(sum("cache_hits" in s for s in seen),
          sum("cache_misses" in s for s in seen))
""")


def _compile(tmp_path, constant: float):
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(sys.path)
    p = subprocess.run([sys.executable, "-c", PROGRAM, str(constant)],
                       env=env, capture_output=True, text=True, timeout=120,
                       check=True)
    hits, misses = map(int, p.stdout.split())
    return hits, misses


def _entries(path):
    return sorted(p.name for p in path.glob(f"*{cache.CACHE_SUFFIX}"))


def test_prune_removes_entries_last_used_before_the_run(tmp_path):
    for key, used in (("old", 10), ("new", 30), ("bare", None)):
        (tmp_path / f"{key}{cache.CACHE_SUFFIX}").write_bytes(b"x")
        if used is not None:
            (tmp_path / f"{key}{cache.ATIME_SUFFIX}").write_bytes(
                used.to_bytes(8, "little"))
    assert cache.prune(tmp_path, since_ns=20) == ["bare", "old"]
    assert sorted(p.name for p in tmp_path.iterdir()) == [
        f"new{cache.ATIME_SUFFIX}", f"new{cache.CACHE_SUFFIX}"]


def test_a_seed_keyed_program_compiles_in_every_run(tmp_path):
    # run 1: constant 1 compiles and is kept
    assert _compile(tmp_path, 1.0) == (0, 1)
    # run 2: constant 2 compiles; the prune leaves only its entry
    t = time.time_ns()
    assert _compile(tmp_path, 2.0) == (0, 1)
    assert len(cache.prune(tmp_path, t)) == 1
    assert len(_entries(tmp_path)) == 1
    # run 3: constant 1 again finds nothing of run 1 and compiles
    t = time.time_ns()
    assert _compile(tmp_path, 1.0) == (0, 1)
    cache.prune(tmp_path, t)
    # run 4: the same constant as the run before finds its entry, which
    # the prune keeps
    t = time.time_ns()
    assert _compile(tmp_path, 1.0) == (1, 0)
    assert cache.prune(tmp_path, t) == []
    assert len(_entries(tmp_path)) == 1
