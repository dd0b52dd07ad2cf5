"""Profiler integration — jax.profiler traces for the step loop.

The reference had no tracing at all (SURVEY §5.1; Spark UI existed but was
unconfigured). Here any run can capture an XLA/TensorBoard trace::

    with profile_to("/tmp/trace"):
        engine.run(...)

The capture holds the engine's host spans (``rtfds.<span>#<batch>``, from
``utils/trace.Tracer``) over device ops whose ``op_name`` carries the step's
stages (``rtfds.<stage>``, ``utils/trace.STEP_SCOPES``): one clock, one
vocabulary, in TensorBoard/xprof.
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
    CLI, ``chip_smoke.py``, the tools — goes through here.

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
    keep_scopes_in_cache_key()
    return jax.config.jax_compilation_cache_dir


def keep_scopes_in_cache_key() -> None:
    """Hash a program for the persistent cache WITH its debug metadata.

    jax's default strips it first, so an executable cached by a build
    whose step carried other ``jax.named_scope``s (or none) is served to
    this one, and a profiler trace then shows that build's ``op_name``s:
    the stage metrics read nothing. The price: a program whose source
    lines moved compiles once more (the metadata holds file and line)."""
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)


@contextlib.contextmanager
def profile_to(log_dir: Optional[str]) -> Iterator[None]:
    """Capture a jax.profiler trace into ``log_dir`` (no-op when None),
    with the process-wide ``Tracer`` on for the capture's duration."""
    if not log_dir:
        yield
        return
    import jax

    from real_time_fraud_detection_system_tpu.utils.trace import get_tracer

    tracer = get_tracer()
    was_enabled = tracer.enabled
    # the engine's spans go into the capture as TraceAnnotations
    tracer.configure(enabled=True)
    # the Python tracer would log every call of the serving loop: a trace
    # too large to load, and the loop slowed by the logging
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
        tracer.configure(enabled=was_enabled)
