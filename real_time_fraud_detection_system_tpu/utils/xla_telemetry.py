"""XLA/device telemetry: compile counters, steady-state recompile
detection, and device-memory gauges.

Round-5 benching had to reverse-engineer device step time from RTT
decomposition, and a silent in-loop retrace costs ~1 s on this hardware
(969 ms measured vs 8 ms steady-state, ``runtime/sharded_engine.py``).
This module makes the XLA layer report instead of being inferred:

- :func:`install_compile_telemetry` hooks ``jax.monitoring``'s
  duration-event stream once per process and turns every backend
  compile into ``rtfds_xla_compiles_total`` + an
  ``rtfds_xla_compile_seconds`` histogram observation, plus an
  ``xla_compile`` span on the active tracer so compiles appear on the
  Perfetto timeline next to the batch phases they stall.
- :class:`RecompileDetector` wraps the engine's jitted step calls. It
  tracks the (shapes, dtypes, donation) signature of every call; a
  compile observed during a call AFTER the warmup window increments
  ``rtfds_xla_recompiles_total`` and warn-logs the signature diff — the
  alarm for shape churn, silent donation loss, or a hot model reload
  that changed the params' shape family mid-serve.
- :class:`DeviceMemoryTelemetry` samples ``device.memory_stats()`` into
  ``rtfds_device_memory_bytes{kind=in_use|peak}`` gauges each batch
  (backends without memory stats — CPU — are detected once and sampling
  becomes a no-op).

Compile events are process-global (the jit cache is process-global), so
the listener always reports into the DEFAULT registry; the per-engine
recompile counter honors the engine's own registry, matching how every
other engine series behaves.
"""

from __future__ import annotations

import threading
import time
from typing import Optional, Tuple

from real_time_fraud_detection_system_tpu.utils.logging import get_logger
from real_time_fraud_detection_system_tpu.utils.metrics import (
    MetricsRegistry,
    get_registry,
)

log = get_logger("xla")

# The jax.monitoring duration event that marks one backend (XLA)
# compilation. Trace/lowering events are reported separately by jax and
# excluded — "a compile" here means "XLA built a new executable".
_COMPILE_EVENT_SUFFIX = "backend_compile_duration"

_install_lock = threading.Lock()
_installed = False
# Monotone count of backend compiles observed since install — the
# RecompileDetector samples deltas of this around each step call.
_compile_count = 0


def install_compile_telemetry() -> bool:
    """Register the ``jax.monitoring`` listener (idempotent; one per
    process). Returns True once the listener is active."""
    global _installed
    with _install_lock:
        if _installed:
            return True
        import jax.monitoring as monitoring

        reg = get_registry()
        m_compiles = reg.counter(
            "rtfds_xla_compiles_total",
            "XLA backend compilations in this process")
        m_seconds = reg.histogram(
            "rtfds_xla_compile_seconds",
            "wall time per XLA backend compilation")

        def _listener(name: str, duration_s: float, **kw) -> None:
            if not name.endswith(_COMPILE_EVENT_SUFFIX):
                return
            global _compile_count
            _compile_count += 1
            m_compiles.inc()
            m_seconds.observe(float(duration_s))
            # Put the compile on the trace timeline: the event fires at
            # compile END, so the span is backdated by its duration.
            from real_time_fraud_detection_system_tpu.utils.trace import (
                get_tracer,
            )

            tracer = get_tracer()
            if tracer.enabled:
                t1 = time.perf_counter()
                tracer.add_span("xla_compile", t1 - float(duration_s), t1)

        monitoring.register_event_duration_secs_listener(_listener)
        _installed = True
        return True


def compile_count() -> int:
    """Backend compiles observed since :func:`install_compile_telemetry`
    (0 until installed)."""
    return _compile_count


def step_signature(*arrays, static: Tuple = ()) -> Tuple:
    """Build a (shapes, dtypes, static) call signature for the recompile
    detector from the arrays an engine step receives. ``static`` carries
    whatever else keys the jit cache (donation layout, model kind,
    routed/local variant)."""
    return tuple(
        (tuple(a.shape), str(getattr(a, "dtype", type(a).__name__)))
        for a in arrays
    ) + tuple(static)


class _StepWindow:
    """Context manager produced by :meth:`RecompileDetector.step`."""

    __slots__ = ("_det", "_sig", "_before")

    def __init__(self, det: "RecompileDetector", sig: Tuple):
        self._det = det
        self._sig = sig

    def __enter__(self):
        self._before = _compile_count
        return self

    def __exit__(self, *exc):
        self._det._after_call(self._sig, _compile_count - self._before)
        return False


class RecompileDetector:
    """Steady-state recompile alarm for a jitted step.

    Warmup semantics: the first ``warmup_calls`` step calls may compile
    freely (bucket-size jit-cache fills are expected there). After
    warmup, ANY compile observed during a tracked step call increments
    ``rtfds_xla_recompiles_total`` and warn-logs the diff between the
    offending call's signature and the known signature set — whether the
    signature is new (late bucket size, reload-changed params shapes:
    a real compile paid inside the serving loop either way) or already
    seen (donation/weak-type/sharding churn: the jit cache is thrashing).

    Requires :func:`install_compile_telemetry`; without a listener the
    compile delta is always 0 and the detector stays silent (never
    wrong, just blind — e.g. a jax-free process importing the engine).
    """

    DEFAULT_WARMUP_CALLS = 4

    def __init__(self, warmup_calls: int = DEFAULT_WARMUP_CALLS,
                 registry: Optional[MetricsRegistry] = None,
                 name: str = "engine_step"):
        self.warmup_calls = int(warmup_calls)
        self.name = name
        reg = registry if registry is not None else get_registry()
        self._m_recompiles = reg.counter(
            "rtfds_xla_recompiles_total",
            "XLA compilations observed during step calls after warmup "
            "(steady-state serving should hold this at 0)")
        self._seen: dict = {}   # signature -> first call index
        self._calls = 0
        self._last_sig: Optional[Tuple] = None

    @property
    def calls(self) -> int:
        return self._calls

    @property
    def recompiles(self) -> float:
        return self._m_recompiles.value

    def step(self, signature: Tuple) -> _StepWindow:
        """Wrap one jitted step call::

            with detector.step(step_signature(jbatch, static=("donate0",))):
                out = self._step(...)
        """
        return _StepWindow(self, signature)

    def _diff(self, sig: Tuple) -> str:
        """Human diff of ``sig`` vs the previous call's signature."""
        prev = self._last_sig
        if prev is None:
            return f"first signature: {sig}"
        if prev == sig:
            return (f"signature unchanged ({sig}) — the retrace is keyed "
                    "on something outside the tracked signature "
                    "(input sharding, weak types, or donation)")
        changed = []
        for i in range(max(len(prev), len(sig))):
            a = prev[i] if i < len(prev) else "<absent>"
            b = sig[i] if i < len(sig) else "<absent>"
            if a != b:
                changed.append(f"arg[{i}]: {a} -> {b}")
        return "; ".join(changed) or f"{prev} -> {sig}"

    def _after_call(self, sig: Tuple, compiles: int) -> None:
        self._calls += 1
        new_sig = sig not in self._seen
        if compiles and self._calls > self.warmup_calls:
            self._m_recompiles.inc(compiles)
            log.warning(
                "%s recompiled at call %d (%d compile%s after a "
                "%d-call warmup): %s",
                self.name, self._calls, compiles,
                "s" if compiles > 1 else "", self.warmup_calls,
                self._diff(sig))
            from real_time_fraud_detection_system_tpu.utils.trace import (
                get_tracer,
            )

            tracer = get_tracer()
            if tracer.enabled:
                tracer.instant("xla_recompile", call=self._calls,
                               signature=str(sig), diff=self._diff(sig))
        if new_sig:
            self._seen[sig] = self._calls
        self._last_sig = sig


class DeviceMemoryTelemetry:
    """Per-batch ``rtfds_device_memory_bytes{kind=in_use|peak}`` gauges.

    Samples ``device.memory_stats()`` for every local device. Backends
    that return no stats (CPU) are detected on the first sample and the
    instance turns itself off — the steady-state cost on such backends
    is a single boolean check per batch."""

    # memory_stats() key -> gauge `kind` label
    _KINDS = (("bytes_in_use", "in_use"), ("peak_bytes_in_use", "peak"))

    def __init__(self, registry: Optional[MetricsRegistry] = None):
        self._reg = registry if registry is not None else get_registry()
        self._devices = None
        self._gauges: dict = {}
        self._dead = False

    def sample(self) -> None:
        if self._dead:
            return
        if self._devices is None:
            import jax

            self._devices = jax.local_devices()
        any_stats = False
        for i, d in enumerate(self._devices):
            try:
                stats = d.memory_stats()
            # rtfdslint: disable=broad-exception-catch (memory_stats is a per-backend C++ binding that can raise arbitrary plugin errors; telemetry must sample-or-skip, never kill the batch)
            except Exception:
                stats = None
            if not stats:
                continue
            any_stats = True
            for key, kind in self._KINDS:
                v = stats.get(key)
                if v is None:
                    continue
                g = self._gauges.get((i, kind))
                if g is None:
                    g = self._reg.gauge(
                        "rtfds_device_memory_bytes",
                        "device memory from memory_stats(), sampled "
                        "per batch", device=str(i), kind=kind)
                    self._gauges[(i, kind)] = g
                g.set(float(v))
        if not any_stats:
            self._dead = True  # CPU-style backend: stop sampling
