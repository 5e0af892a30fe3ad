"""Persistent-compilation-cache switch + warm-vs-cold compile benchmark.

Compile time is pure overhead the perf loop pays on every cold process —
for the big sweep programs it dwarfs the first measured steady-state call
(the N=1e6 edge-sharded program spends minutes in XLA before the first
step runs). jax ships an on-disk executable cache
(``jax_compilation_cache_dir``); :func:`enable` turns it on for the whole
harness (``benchmarks/run.py``) and for ``chip_smoke.py``, rooted at
``JAX_COMPILATION_CACHE_DIR`` when that is set and at ``<checkout>/.jax-cache``
otherwise. The CI bench lane persists that directory across workflow
runs, so re-benchmarking an unchanged program costs a deserialization,
not a compile.

:func:`rows` pins the claim with two rows over the same lowered program:

* ``compile_sweep_cold`` — first ``.compile()`` in this process. A real
  XLA compile when the on-disk cache is empty (``cache=miss``), a disk
  read when a previous run populated it (``cache=hit``) — which one
  happened is detected by whether the compile wrote a new cache entry and
  recorded in the derived tag.
* ``compile_sweep_warm`` — ``jax.clear_caches()`` then recompile: with
  the persistent cache on this is always disk-served, so warm << cold on
  any first (miss) run is the cache working end-to-end.

Both rows are tagged ``gate=off``: compiler wall time jitters far beyond
the perf gate's budget and measures XLA + disk, not the engines.
"""
import glob
import os
import time

import jax
import numpy as np

from repro.core.graphs import random_strongly_connected_edge_list
from repro.core.pushsum import run_pushsum_sparse


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_DIR = os.path.join(REPO, ".jax-cache")


def enable() -> str:
    """Turn on jax's persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax already roots the cache
    there and nothing is set here. Otherwise the cache lives at the fixed
    ``<checkout>/.jax-cache`` — the path is part of the cache's key, so a
    directory that moved (a temp name, a pid, a time) would never hit.
    The min-compile-time / min-entry-size floors are dropped to zero so
    the CI smoke programs (which compile in well under a second) are
    cached too. Call before the first compile.
    """
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


def _cache_dir() -> str | None:
    return jax.config.jax_compilation_cache_dir


def _cache_entries(cache_dir: str | None) -> int:
    if not cache_dir or not os.path.isdir(cache_dir):
        return 0
    return len(glob.glob(os.path.join(cache_dir, "**"), recursive=True))


def rows(smoke: bool = False):
    n, d, T = (256, 2, 20) if smoke else (512, 4, 50)
    rng = np.random.default_rng(0)
    el = random_strongly_connected_edge_list(n, 2.0, rng)
    w = rng.normal(size=(n, d)).astype(np.float32)

    fn = jax.jit(lambda w_, s_, d_: run_pushsum_sparse(
        w_, s_, d_, T, drop_prob=0.2, B=4, record_every=T)[1])

    cache_dir = _cache_dir()
    before = _cache_entries(cache_dir)
    lowered = fn.lower(w, el.src, el.dst)
    t0 = time.perf_counter()
    lowered.compile()
    cold_s = time.perf_counter() - t0
    if cache_dir is None:
        cache = "off"
    elif _cache_entries(cache_dir) > before:
        cache = "miss"            # a real compile wrote a new entry
    elif before > 1:
        cache = "hit"             # served from a pre-populated cache
    else:
        # empty dir and nothing written: the cache is configured but not
        # taking entries (enable() called after the first compile) — say
        # so instead of mislabeling it a hit
        cache = "uncached"

    # drop the in-memory executable so the second compile must go back to
    # the persistent layer (or recompile, when the cache is off)
    jax.clear_caches()
    lowered = fn.lower(w, el.src, el.dst)
    t0 = time.perf_counter()
    lowered.compile()
    warm_s = time.perf_counter() - t0

    warm_cache = ("off" if cache_dir is None
                  else "hit" if _cache_entries(cache_dir) > 1
                  else "uncached")
    base = f"N={n};d={d};T={T};gate=off"
    return [
        ("compile_sweep_cold", cold_s * 1e6, f"{base};cache={cache}"),
        ("compile_sweep_warm", warm_s * 1e6,
         f"{base};cache={warm_cache};"
         f"speedup_vs_cold={cold_s / max(warm_s, 1e-9):.1f}x"),
    ]
