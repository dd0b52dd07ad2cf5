"""Profiler integration — jax.profiler traces for the step loop.

The reference had no tracing at all (SURVEY §5.1; Spark UI existed but was
unconfigured). Here any run can capture an XLA/TensorBoard trace::

    with profile_to("/tmp/trace"):
        engine.run(...)

and individual host-side phases can be annotated with ``trace_span`` so they
show up on the profiler timeline next to device ops.
"""

from __future__ import annotations

import contextlib
import os
from typing import Iterator, Optional


def _repo_cache_dir() -> str:
    """``<checkout>/.jax_cache`` — resolved from this file like
    ``core/native.py::_repo_root``, so every process started from the
    same checkout computes the same path (the directory is part of the
    cache key: one that moves never hits)."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(root, ".jax_cache")


# Programs that compile faster than this are not worth a cache entry.
CACHE_MIN_COMPILE_S = 0.1


def enable_compilation_cache() -> str:
    """Turn on jax's persistent XLA compilation cache; returns its dir.

    One rule. ``JAX_COMPILATION_CACHE_DIR`` set: jax reads it itself and
    this function sets no directory in code. Unset: the fixed
    in-checkout ``.jax_cache`` (git-ignored). Every entry point — the
    CLI, ``bench.py``, ``chip_smoke.py``, the tools — goes through here.

    The minimum-compile-time threshold drops from jax's 1 s to
    ``CACHE_MIN_COMPILE_S`` so the serving step programs are actually
    written: the forest step compiles for a v5e in 2-6 s and the small
    buckets well under a second. It stays above zero so the hundreds of
    one-op eager programs a run compiles in milliseconds (a zero floor
    wrote 315 entries in two smoke runs) are not. Nothing is caught: a
    cache that cannot be enabled is an error, not a slower run."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", _repo_cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      CACHE_MIN_COMPILE_S)
    return jax.config.jax_compilation_cache_dir


@contextlib.contextmanager
def profile_to(log_dir: Optional[str]) -> Iterator[None]:
    """Capture a jax.profiler trace into ``log_dir`` (no-op when None)."""
    if not log_dir:
        yield
        return
    import jax

    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


@contextlib.contextmanager
def trace_span(name: str) -> Iterator[None]:
    """Named host-side span on the profiler timeline."""
    import jax

    with jax.profiler.TraceAnnotation(name):
        yield
