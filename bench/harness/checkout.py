"""Where the benchmark lives and what it keeps beside the checkout.

``setup_process`` runs before JAX is imported: it puts the program's
``src`` on the path and fixes JAX's persistent compilation cache at
``<checkout>/.jax_cache`` (listed in ``.gitignore``).  The path is part of
the cache key, so it never depends on the time, a process id or a
temporary name; the program's own ``enable_compile_cache`` takes the same
directory from ``JAX_COMPILATION_CACHE_DIR``.
"""
from __future__ import annotations

import os
import pathlib
import sys

CHECKOUT = pathlib.Path(__file__).resolve().parents[2]
BENCH = CHECKOUT / "bench"
CACHE_DIR = CHECKOUT / ".jax_cache"
TRACE_DIR = CHECKOUT / ".bench_trace"


def setup_process() -> None:
    src = str(CHECKOUT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    if str(CHECKOUT) not in sys.path:
        sys.path.insert(0, str(CHECKOUT))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    # the TPU compiler logs under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def enable_cache() -> bool:
    """Cache every program, however quick its compile, so a cell's second
    run in a checkout loads all of them instead of compiling the small
    ones again inside its set-up.  Returns whether the cache was empty
    (the checkout's first run, which compiles everything)."""
    import jax

    cold = not (CACHE_DIR.is_dir() and any(CACHE_DIR.iterdir()))
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cold


def missing_program() -> str:
    """Name what a run needs from the checkout beyond the benchmark's own
    files, or return an empty string when it is all there."""
    need = [CHECKOUT / "src" / "repro" / "serve" / "engine.py"]
    gone = [str(p.relative_to(CHECKOUT)) for p in need if not p.exists()]
    return ", ".join(gone)
