"""Failure detection, retry policies, fault injection, supervised recovery.

The reference's resilience machinery is compose-level only (SURVEY §5.3):
healthchecks + ``restart:`` policies (``docker-compose.yml:83-87,133``), the
datagen 4×5 s connect retry (``datagen/data_gen.py:72-80``), tolerated model
-download 404s (``fraud_detection.py:73-79``), and Spark checkpoint replay.
It has **no fault injection at all**. This module provides the in-process
equivalents plus the missing injection tools:

- :class:`RetryPolicy` / :func:`with_retries` — exponential-backoff retry,
  the ``psycopg2`` connect-loop analogue;
- :class:`Heartbeat` — stall detection for the micro-batch loop (the
  healthcheck role: no progress for ``timeout_s`` → unhealthy);
- :class:`FlakySource` / :func:`corrupt_messages` /
  :class:`PoisonSource` / :func:`poison_messages` — deterministic fault
  injectors: scripted transient poll failures (source wrapper), scripted
  envelope corruption (message transform), and scripted poison pills
  (rows that deterministically crash ingest on every replay);
- :func:`run_with_recovery` — the ``restart: on-failure`` supervisor: on a
  crash, rebuild the engine state from the last checkpoint, seek the
  source, resume; exactly-once at micro-batch granularity because offsets
  and state are checkpointed atomically together (``io/checkpoint.py``).
  Unlike Spark's replay contract (which only helps when failures are
  transient), the supervisor DIAGNOSES failures: K consecutive crashes at
  the same resume point reclassify the failure from transient to poison,
  the offending micro-batch is bisected down to the minimal failing row
  set against a pre-batch state snapshot, those rows land in a
  dead-letter queue, and the stream continues past them — at-most-K
  restarts per poison batch instead of stream death.
"""

from __future__ import annotations

import random
import time
import uuid
from dataclasses import dataclass
from typing import Callable, Optional, Sequence, Tuple, Type

import numpy as np

from real_time_fraud_detection_system_tpu.utils.logging import get_logger
from real_time_fraud_detection_system_tpu.utils.metrics import (
    active_recorder,
    get_registry,
)

log = get_logger("faults")


def _record_fault(kind: str, count: int = 1, **fields) -> None:
    """Count an injected fault (by kind) and land it in the flight
    record, so a chaos run's telemetry shows exactly which failures were
    scripted vs organic."""
    get_registry().counter(
        "rtfds_faults_injected_total", "injected faults by kind",
        kind=kind).inc(count)
    rec = active_recorder()
    if rec is not None:
        rec.record_event("fault", fault_kind=kind, count=count, **fields)


class TransientError(RuntimeError):
    """An injected or genuinely transient failure — safe to retry."""


class StallError(TransientError):
    """The watchdog found no engine progress within the stall budget."""


class PoisonRowError(TransientError):
    """A batch contained row(s) that fail ingest validation (corrupt
    envelope values that decoded structurally but carry impossible
    content, e.g. a negative amount).

    Subclasses :class:`TransientError` deliberately: at the moment it is
    raised, the supervisor cannot tell a corrupt record from a transient
    infrastructure hiccup — both look like "the batch crashed". The
    crash-loop breaker in :func:`run_with_recovery` resolves exactly that
    ambiguity: a failure that recurs at the same resume point is
    reclassified from transient to poison and quarantined via bisection,
    whatever its exception type.
    """


@dataclass(frozen=True)
class RetryPolicy:
    """Exponential backoff: delay = base * multiplier^attempt (capped).

    ``multiplier`` defaults to 1.0 — the reference's constant-5 s connect
    loop (``datagen/data_gen.py:72-80`` sleeps the same 5 s every try);
    pass > 1.0 for genuine exponential growth. ``jitter`` is the fraction
    of each delay randomized away: the slept time is uniform in
    ``[(1 - jitter) * d, d]``, so ``jitter=1.0`` is classic full jitter —
    the thundering-herd guard for fleet-wide reconnects (a thousand
    workers that all lost the same broker must not all come back on the
    same tick). ``delay()`` stays deterministic; only the slept time
    (:meth:`sleep_s`) jitters.
    """

    max_attempts: int = 4
    base_delay_s: float = 5.0
    multiplier: float = 1.0  # reference uses constant 5 s sleeps
    max_delay_s: float = 60.0
    jitter: float = 0.0  # 0 = deterministic; 1.0 = full jitter

    def __post_init__(self):
        if self.max_attempts < 1:
            raise ValueError("max_attempts must be >= 1")
        if not 0.0 <= self.jitter <= 1.0:
            raise ValueError("jitter must be a fraction in [0, 1]")

    def delay(self, attempt: int) -> float:
        return min(self.base_delay_s * self.multiplier**attempt,
                   self.max_delay_s)

    def sleep_s(self, attempt: int,
                rand: Callable[[], float] = random.random) -> float:
        """The (possibly jittered) time to actually sleep for ``attempt``."""
        d = self.delay(attempt)
        if self.jitter <= 0.0:
            return d
        return d * (1.0 - self.jitter * rand())


def with_retries(
    fn: Callable,
    policy: RetryPolicy = RetryPolicy(),
    retry_on: Tuple[Type[BaseException], ...] = (TransientError,),
    sleep: Callable[[float], None] = time.sleep,
):
    """Call ``fn()`` with up to ``max_attempts`` tries (the datagen connect
    loop, ``data_gen.py:72-80``). Non-listed exceptions propagate at once.
    Each retried attempt lands in ``rtfds_retry_attempts_total{outcome=
    retried}``; a run that exhausts the budget lands one
    ``outcome=exhausted`` sample before re-raising."""
    reg = get_registry()
    m_retried = reg.counter(
        "rtfds_retry_attempts_total", "with_retries attempts by outcome",
        outcome="retried")
    m_exhausted = reg.counter(
        "rtfds_retry_attempts_total", "with_retries attempts by outcome",
        outcome="exhausted")
    last: Optional[BaseException] = None
    for attempt in range(policy.max_attempts):
        try:
            return fn()
        except retry_on as e:  # noqa: PERF203 — retry loop by design
            last = e
            if attempt + 1 < policy.max_attempts:
                d = policy.sleep_s(attempt)
                m_retried.inc()
                log.warning("attempt %d/%d failed (%s); retrying in %.1fs",
                            attempt + 1, policy.max_attempts, e, d)
                sleep(d)
    m_exhausted.inc()
    raise last  # type: ignore[misc]


class Heartbeat:
    """Progress-based failure detector (the compose healthcheck role).

    ``beat()`` on every engine loop pass (:func:`run_with_recovery` wires
    the heartbeat into ``engine.run``); ``healthy()`` is False once
    ``timeout_s`` passes with no beat. :func:`run_with_recovery` watches it
    from a supervisor thread and escalates a stall into the restart path
    (:class:`StallError`) — a silently hung source or device step is
    recovered from like a crash, not waited on forever.
    """

    def __init__(self, timeout_s: float = 60.0,
                 clock: Callable[[], float] = time.monotonic):
        self.timeout_s = timeout_s
        self._clock = clock
        self._last = clock()
        self.beats = 0

    def beat(self) -> None:
        self._last = self._clock()
        self.beats += 1

    def healthy(self) -> bool:
        return (self._clock() - self._last) <= self.timeout_s

    def seconds_since_beat(self) -> float:
        return self._clock() - self._last


class HangingSource:
    """Wraps a source; scripted poll indices HANG (block silently) instead
    of raising — the failure mode retries can't see and only a watchdog
    catches (a wedged Kafka client, a stuck NFS read).

    Each scripted hang fires once: the incarnation that hit it stays
    blocked (until ``release`` or ``max_hang_s``), and the restarted
    incarnation's polls proceed — modeling a connection that is re-opened
    by the restart while the old one stays wedged.
    """

    def __init__(self, inner, hang_at: Sequence[int] = (),
                 max_hang_s: float = 60.0):
        import threading

        self.inner = inner
        self.hang_at = set(int(i) for i in hang_at)
        self.max_hang_s = max_hang_s
        self.release = threading.Event()
        self._polls = 0

    def poll_batch(self):
        i = self._polls
        self._polls += 1
        if i in self.hang_at:
            self.hang_at.discard(i)
            _record_fault("hang", poll=i)
            self.release.wait(timeout=self.max_hang_s)  # silent stall
        return self.inner.poll_batch()

    @property
    def offsets(self):
        return self.inner.offsets

    def seek(self, offsets):
        self.inner.seek(offsets)


class FlakySource:
    """Wraps a source; raises TransientError on scripted poll indices.

    ``fail_at`` lists 0-based poll indices that raise *instead of* returning
    the batch; the underlying source is only advanced on success, so a
    retried poll returns the batch the failure swallowed — exactly like a
    Kafka consumer that died before committing.
    """

    def __init__(self, inner, fail_at: Sequence[int] = ()):
        self.inner = inner
        self.fail_at = set(int(i) for i in fail_at)
        self._polls = 0

    def poll_batch(self):
        i = self._polls
        self._polls += 1
        if i in self.fail_at:
            _record_fault("flaky_poll", poll=i)
            raise TransientError(f"injected poll failure #{i}")
        return self.inner.poll_batch()

    @property
    def offsets(self):
        return self.inner.offsets

    def seek(self, offsets):
        self.inner.seek(offsets)


class PoisonSource:
    """Wraps a source; scripted ``tx_id`` rows are served CORRUPTED
    (negated amount) on EVERY poll that contains them.

    The deterministic poison-pill injector: unlike
    :func:`corrupt_messages` (whose truncated envelopes the decoder
    masks), a poisoned row decodes structurally fine and then fails the
    engine's ingest validation (:class:`PoisonRowError`) — so a
    checkpoint replay re-polls the same rows, re-corrupts them, and
    crashes again, exactly the crash loop the supervisor's breaker +
    bisection + dead-letter path exists to survive. Works on any
    columnar ``poll_batch`` source.
    """

    def __init__(self, inner, poison_tx_ids: Sequence[int] = ()):
        self.inner = inner
        self.poison_tx_ids = frozenset(int(i) for i in poison_tx_ids)
        self._ids = np.fromiter(sorted(self.poison_tx_ids), dtype=np.int64,
                                count=len(self.poison_tx_ids))

    def poll_batch(self):
        cols = self.inner.poll_batch()
        if cols is None or not len(self.poison_tx_ids):
            return cols
        tx = cols.get("tx_id")
        if tx is None or len(tx) == 0:
            return cols
        mask = np.isin(np.asarray(tx), self._ids)
        if mask.any():
            cols = dict(cols)
            amt = np.array(cols["tx_amount_cents"], copy=True)
            amt[mask] = -np.abs(amt[mask]) - 1
            cols["tx_amount_cents"] = amt
            _record_fault("poison", count=int(mask.sum()))
        return cols

    @property
    def offsets(self):
        return self.inner.offsets

    def seek(self, offsets):
        self.inner.seek(offsets)

    def commit(self) -> None:
        commit = getattr(self.inner, "commit", None)
        if commit is not None:
            commit()

    def close(self) -> None:
        close = getattr(self.inner, "close", None)
        if close is not None:
            close()


class FlakyStore:
    """Wraps an :mod:`..io.store` object; scripted PUT/GET op indices
    raise ``ConnectionError`` instead of touching the store.

    The durable-state twin of :class:`FlakySource`: a checkpoint save or
    restore that hits a scripted failure looks exactly like a flaky
    S3/MinIO endpoint (same exception family the hardened
    ``StoreCheckpointer`` retries on), and the underlying store is only
    touched on success — so a retried op performs the work the failure
    swallowed, never half of it. ``fail_puts``/``fail_gets`` are 0-based
    per-verb op indices.
    """

    def __init__(self, inner, fail_puts: Sequence[int] = (),
                 fail_gets: Sequence[int] = ()):
        self.inner = inner
        self.fail_puts = set(int(i) for i in fail_puts)
        self.fail_gets = set(int(i) for i in fail_gets)
        self._puts = 0
        self._gets = 0

    def put(self, key: str, data: bytes) -> None:
        i = self._puts
        self._puts += 1
        if i in self.fail_puts:
            _record_fault("flaky_store_put", op=i, key=key)
            raise ConnectionError(f"injected store PUT failure #{i}")
        self.inner.put(key, data)

    def get(self, key: str) -> bytes:
        i = self._gets
        self._gets += 1
        if i in self.fail_gets:
            _record_fault("flaky_store_get", op=i, key=key)
            raise ConnectionError(f"injected store GET failure #{i}")
        return self.inner.get(key)

    def __getattr__(self, name):
        return getattr(self.inner, name)


class TornStore:
    """Wraps a store; the scripted PUT lands TRUNCATED — and succeeds.

    The torn-write injector: unlike :class:`FlakyStore` (whose failures
    the caller can see and retry), a torn PUT reports success while
    storing only the first ``keep_bytes`` of the payload — the
    silent-partial-write failure mode only restore-time verification
    (checkpoint format v2 manifests) can catch. ``tear_at`` is the
    0-based PUT op index to tear; every other op passes through.
    """

    def __init__(self, inner, tear_at: int = 0, keep_bytes: int = 64):
        self.inner = inner
        self.tear_at = int(tear_at)
        self.keep_bytes = int(keep_bytes)
        self._puts = 0

    def put(self, key: str, data: bytes) -> None:
        i = self._puts
        self._puts += 1
        if i == self.tear_at:
            _record_fault("torn_store_put", op=i, key=key,
                          kept=min(self.keep_bytes, len(data)),
                          dropped=max(0, len(data) - self.keep_bytes))
            self.inner.put(key, data[: self.keep_bytes])
            return  # reports success: the tear is silent by design
        self.inner.put(key, data)

    def __getattr__(self, name):
        return getattr(self.inner, name)


def poison_messages(msgs: Sequence[bytes],
                    poison_at: Sequence[int] = ()) -> list:
    """Envelope-level poison injection: re-encode scripted messages with
    a negated amount.

    The corrupted-producer analogue of :func:`corrupt_messages`, one
    notch nastier: the envelope still parses (the decoder can NOT mask
    it), so the impossible value reaches the engine's ingest validation
    and crashes the batch deterministically on every replay. Produce the
    result into a broker/topic to exercise the full poison path."""
    from real_time_fraud_detection_system_tpu.core.envelope import (
        decode_transaction_envelopes_fast,
        encode_transaction_envelopes,
    )

    idxs = sorted(set(int(i) for i in poison_at) if poison_at else ())
    idxs = [i for i in idxs if 0 <= i < len(msgs)]
    out = list(msgs)
    if not idxs:
        return out
    cols, invalid = decode_transaction_envelopes_fast(
        [msgs[i] for i in idxs])
    poisoned = encode_transaction_envelopes(
        cols["tx_id"], cols["tx_datetime_us"], cols["customer_id"],
        cols["terminal_id"], -np.abs(cols["tx_amount_cents"]) - 1,
    )
    n = 0
    for j, i in enumerate(idxs):
        if invalid[j]:
            continue  # already-corrupt envelope: leave it to the decoder
        out[i] = poisoned[j]
        n += 1
    if n:
        _record_fault("poison_envelope", count=n)
    return out


def corrupt_messages(msgs: Sequence[bytes],
                     corrupt_every: int = 17) -> list:
    """Envelope-level fault injection: truncate every k-th message.

    Corrupt envelopes must be masked by the decoder, never crash the batch
    (the golden-decode robustness property, SURVEY §4). Produce the result
    into a broker/topic to exercise the full envelope path."""
    k = max(int(corrupt_every), 1)
    out = [
        m[: max(len(m) // 2, 1)] if i % k == k - 1 else m
        for i, m in enumerate(msgs)
    ]
    n_corrupt = len(msgs) // k
    if n_corrupt:
        _record_fault("corrupt_envelope", count=n_corrupt)
    return out


class _FencedCheckpointer:
    """Restores only checkpoints saved through THIS wrapper.

    Used by :func:`run_with_recovery` when ``resume=False``: a stale
    checkpoint left by a previous run must never be restored by a crash
    incarnation of a run that explicitly asked for a fresh start. The
    pre-existing checkpoint files are recorded at construction and left
    untouched until this run's FIRST save — if the fresh run dies before
    ever saving, the previous run's checkpoints remain resumable. The
    first save supersedes the old lineage: the stale files are renamed
    aside (``stale-<token>-ckpt-…``, bytes preserved, unique token so
    repeated fresh runs never clobber each other's stash) so they are
    invisible to ``latest()`` AND to the retention GC — otherwise `keep`
    stale higher-numbered files would garbage-collect this run's first
    saves the moment they land.
    """

    def __init__(self, inner):
        self.inner = inner
        self._saved: list = []
        # Lineage API (Checkpointer AND StoreCheckpointer provide it):
        # record the pre-existing checkpoints to quarantine on first save.
        self._stale: list = list(inner.list_checkpoints())

    def save(self, engine_state):
        if self._stale:
            self.inner.quarantine(self._stale, uuid.uuid4().hex[:8])
            self._stale = []
        path = self.inner.save(engine_state)
        self._saved.append(path)
        return path

    def restore(self, engine_state, path=None):
        if path is None:
            # inner.exists filters saves the inner's own GC removed —
            # storage-agnostic (os.path.exists would wrongly drop every
            # object-store key).
            mine = [p for p in self._saved if self.inner.exists(p)]
            if not mine:
                return None
            path = max(mine)
        return self.inner.restore(engine_state, path=path)

    def __getattr__(self, name):
        return getattr(self.inner, name)


class _AbandonFence:
    """Shared flag: flipped when the watchdog abandons an incarnation."""

    def __init__(self):
        self.abandoned = False

    def check(self) -> None:
        if self.abandoned:
            raise StallError("incarnation abandoned by the watchdog")


class _FenceGuard:
    """Proxy that cuts a zombie incarnation off from shared objects.

    Every attribute access (method call, ``offsets`` property, heartbeat
    ``beat``) first checks the fence: once the watchdog abandons the
    incarnation, the zombie's next interaction with the source, sink,
    checkpointer, or heartbeat raises :class:`StallError` inside the
    zombie thread — it cannot steal batches from the restarted
    incarnation, overwrite the live checkpoint with stale state, append
    stale results, or mask real stalls by beating the shared heartbeat.
    (Whole checkpoints are atomic snapshots, so a save that *completes*
    just before abandonment is still consistent.)
    """

    def __init__(self, inner, fence: _AbandonFence):
        object.__setattr__(self, "_inner", inner)
        object.__setattr__(self, "_fence", fence)

    def __getattr__(self, name):
        fence = object.__getattribute__(self, "_fence")
        inner = object.__getattribute__(self, "_inner")
        attr = getattr(inner, name)
        if callable(attr):
            def _guarded(*a, **k):
                fence.check()
                return attr(*a, **k)

            return _guarded
        fence.check()
        return attr

    def __setattr__(self, name, value):
        fence = object.__getattribute__(self, "_fence")
        fence.check()
        setattr(object.__getattribute__(self, "_inner"), name, value)


class _GuardedSource(_FenceGuard):
    """Source fence with a post-poll check.

    Beyond the inherited pre-access checks, a poll that was already in
    flight when the watchdog abandoned this incarnation needs one more
    check AFTER it returns: when the hang finally releases, the zombie's
    poll may have consumed rows from a source SHARED with the restarted
    incarnation. The post-check drops that batch and kills the zombie —
    at-most-one-batch loss in that double-fault race, never a mis-seek.
    The clean fix is not sharing the cursor at all: pass ``make_source``
    to :func:`run_with_recovery` so each incarnation owns a fresh source
    session (what a real Kafka deployment gets from consumer-group
    generation fencing: a zombie consumer's partitions are revoked, its
    late poll cannot commit).
    """

    def poll_batch(self):
        fence = object.__getattribute__(self, "_fence")
        inner = object.__getattribute__(self, "_inner")
        fence.check()
        cols = inner.poll_batch()
        fence.check()  # in-flight poll that outlived abandonment: drop
        return cols


def _fence_model_reload(model_reload, fence: "_AbandonFence"):
    """Fence a reload poll AND keep the shared signature baseline
    honest: a poll whose incarnation was abandoned DURING the call (a
    store GET stalled long enough for the watchdog to give up) may have
    committed the file's new signature to the cross-incarnation
    baseline (``poll.sig_state``, ``--learn-registry`` mode) while its
    swap can never land — every fenced apply path is closed to a
    zombie. Restore the pre-call signature so the LIVE incarnation's
    next poll still sees the change. If the live one updated the
    baseline meanwhile this rolls it back one step and it redundantly
    re-applies the same artifact next poll — the safe direction;
    silently losing the update is not."""
    sig = getattr(model_reload, "sig_state", None)

    def _fenced_reload():
        fence.check()
        before = sig.get("sig") if sig is not None else None
        out = model_reload()
        try:
            fence.check()
        except StallError:
            if sig is not None:
                sig["sig"] = before
            raise
        return out

    return _fenced_reload


def _run_watched(engine, source, sink, checkpointer, max_batches,
                 heartbeat: Heartbeat, feedback=None, model_reload=None,
                 learning=None, target=None):
    """Run one engine incarnation under a stall watchdog.

    The engine loop runs in a worker thread beating the heartbeat each
    pass; this (supervisor) thread polls ``healthy()``. On a stall the
    worker is ABANDONED — a thread blocked in a hung syscall/device call
    cannot be killed — and :class:`StallError` escalates into the restart
    path. The abandoned worker is fenced (:class:`_FenceGuard`): when its
    hang eventually releases, its first touch of the shared source, sink,
    checkpointer, or heartbeat raises and the zombie dies, instead of
    corrupting the restarted incarnation's stream.

    ``target`` replaces the default ``engine.run`` body with another
    supervised workload run over the SAME guarded objects — it is called
    as ``target(g_source, g_sink, g_checkpointer, g_heartbeat)``. Poison
    isolation runs through this, so a batch that HANGS (instead of
    crashing) mid-diagnosis is still bounded by the stall budget.
    """
    import threading

    box: dict = {}
    fence = _AbandonFence()
    g_source = _GuardedSource(source, fence)
    g_sink = _FenceGuard(sink, fence) if sink is not None else None
    g_ckpt = _FenceGuard(checkpointer, fence) if checkpointer is not None \
        else None
    g_heartbeat = _FenceGuard(heartbeat, fence)
    g_feedback = _FenceGuard(feedback, fence) if feedback is not None \
        else None
    # The learning loop outlives incarnations (its learner thread keeps
    # the replay window warm across restarts) — fence THIS incarnation's
    # handle so a zombie's promotion decision can never swap params on
    # the live incarnation's engine.
    g_learning = _FenceGuard(learning, fence) if learning is not None \
        else None
    g_model_reload = (_fence_model_reload(model_reload, fence)
                      if model_reload is not None else None)
    if getattr(engine, "feature_cache", None) is not None:
        # The cache outlives incarnations (it's how the feedback join
        # finds rows scored before a restart) — fence THIS incarnation's
        # handle so a zombie can't overwrite rows the live incarnation
        # re-scored (or reset their labeled marks, double-applying
        # additive label scatters).
        engine.feature_cache = _FenceGuard(engine.feature_cache, fence)
    if learning is not None:
        # The shadow score cache and learner queue outlive incarnations
        # just like the feature cache — attach now (idempotent: the
        # engine.run attach becomes a no-op for this engine) and fence
        # the handles the attach installed, so a zombie that wakes
        # mid-_finish can't write stale champion/candidate scores into
        # the shared shadow cache or stale rows into the learner queue.
        learning.attach(engine)
        if engine.shadow is not None:
            engine.shadow = _FenceGuard(engine.shadow, fence)
        if engine.feedback_tap is not None:
            _tap = engine.feedback_tap

            def _fenced_tap(*a, **k):
                fence.check()
                return _tap(*a, **k)

            engine.feedback_tap = _fenced_tap

    def _target():
        try:
            if target is not None:
                box["stats"] = target(g_source, g_sink, g_ckpt,
                                      g_heartbeat)
            else:
                box["stats"] = engine.run(
                    g_source, sink=g_sink, checkpointer=g_ckpt,
                    max_batches=max_batches, heartbeat=g_heartbeat,
                    feedback=g_feedback, model_reload=g_model_reload,
                    learning=g_learning,
                )
        # rtfdslint: disable=broad-exception-catch (thread-boundary transport: the ORIGINAL exception object crosses to the supervisor thread, which applies the typed recover_on policy — narrowing here would strip the taxonomy, not preserve it)
        except BaseException as e:  # report into the supervisor thread
            box["err"] = e

    heartbeat.beat()  # incarnation start = progress
    worker = threading.Thread(target=_target, daemon=True,
                              name="engine-incarnation")
    worker.start()
    poll = min(max(heartbeat.timeout_s / 4.0, 0.01), 1.0)
    while worker.is_alive():
        worker.join(poll)
        if worker.is_alive() and not heartbeat.healthy():
            fence.abandoned = True
            raise StallError(
                f"no engine progress for "
                f"{heartbeat.seconds_since_beat():.1f}s (stall budget "
                f"{heartbeat.timeout_s:.1f}s); abandoning hung incarnation"
            )
    if "err" in box:
        raise box["err"]
    return box["stats"]


def _subset_cols(cols: dict, idx) -> dict:
    return {k: np.asarray(v)[idx] for k, v in cols.items()}


def _bisect_poison_rows(engine, snapshot: bytes, cols: dict,
                        recover_on,
                        heartbeat=None) -> Tuple[np.ndarray, dict]:
    """Minimal failing row set of a poison batch, by recursive halving.

    Every probe first restores the engine's full state from the
    pre-batch ``snapshot`` (``io/checkpoint.state_to_bytes`` payload), so
    probing never corrupts feature state, counters, or offsets — the
    probes are pure questions. A subset that fails only in combination
    (both halves pass alone, the union crashes) is quarantined whole
    rather than looping forever. Returns ``(bad_row_indices,
    {row_index: exception})``; the engine is left restored to the
    pre-batch snapshot. Probe count is O(k log n) for k poison rows.
    """
    from real_time_fraud_detection_system_tpu.io.checkpoint import (
        bytes_to_state,
    )

    n = len(next(iter(cols.values())))
    bad: list = []
    errors: dict = {}

    def probe(idx) -> Optional[BaseException]:
        if heartbeat is not None:
            # each probe is real progress — keep the watchdog satisfied
            # through a long bisection
            heartbeat.beat()
        bytes_to_state(snapshot, engine.state)
        try:
            engine.process_batch(_subset_cols(cols, idx))
            return None
        except recover_on as e:
            return e

    def rec(idx) -> None:
        e = probe(idx)
        if e is None:
            return
        if len(idx) == 1:
            i = int(idx[0])
            bad.append(i)
            errors[i] = e
            return
        before = len(bad)
        mid = len(idx) // 2
        rec(idx[:mid])
        rec(idx[mid:])
        if len(bad) == before:
            # interaction-dependent failure: halves pass alone, the
            # union crashes — quarantine the whole subset (conservative,
            # terminates)
            for i in idx:
                bad.append(int(i))
                errors[int(i)] = e

    rec(np.arange(n))
    bytes_to_state(snapshot, engine.state)  # leave pre-batch state
    return np.asarray(sorted(set(bad)), dtype=np.int64), errors


def _run_poison_isolation(engine, source, sink, checkpointer, dead_letter,
                          max_batches: int, recover_on,
                          heartbeat: Optional[Heartbeat] = None) -> int:
    """One careful incarnation: step batch-by-batch until the crash-
    looping micro-batch is found, bisect it, quarantine the minimal
    failing row set to the dead-letter queue, score + sink the
    survivors, and checkpoint PAST the poison batch.

    Runs unpipelined with a pre-batch state snapshot per step (the cost
    that makes this a diagnosis mode, not the serving loop); control
    returns to the normal supervisor loop after the first quarantine, a
    clean-batch budget (the crash can only live within one checkpoint
    cadence of the resume point — beyond that the classification was a
    same-point transient after all), stream end, or ``max_batches``.
    Failures that are NOT row-shaped (the poll itself raising) propagate
    to the supervisor and count as ordinary crashes. Returns the number
    of rows quarantined (0 when the suspect batch replayed clean).
    """
    from real_time_fraud_detection_system_tpu.io.checkpoint import (
        bytes_to_state,
        state_to_bytes,
    )
    from real_time_fraud_detection_system_tpu.runtime.engine import (
        empty_batch_result,
    )

    every = int(getattr(engine.cfg.runtime, "checkpoint_every_batches", 50)
                or 50)
    clean_budget = 2 * every + 8
    clean = 0
    quarantined = 0
    rec = active_recorder()
    log.warning("poison isolation: stepping batch-by-batch from batch %d",
                engine.state.batches_done)
    while True:
        if heartbeat is not None:
            heartbeat.beat()
        if max_batches and engine.state.batches_done >= max_batches:
            break
        if clean >= clean_budget:
            # a whole checkpoint cadence replayed clean: the crash loop
            # was a same-point transient, not poison — resume fast mode
            log.info("poison isolation: %d clean batches, no crash — "
                     "reclassifying as transient and resuming", clean)
            break
        snapshot = state_to_bytes(engine.state)
        cols = source.poll_batch()  # a poll crash is not row-poison
        if cols is None:
            break
        if len(next(iter(cols.values()), ())) == 0:
            break  # idle live source: hand back to the paced normal loop
        offsets = list(source.offsets)
        try:
            res = engine.process_batch(cols)
        except recover_on as e:
            bad_idx, errors = _bisect_poison_rows(
                engine, snapshot, cols, recover_on, heartbeat=heartbeat)
            batch_index = int(engine.state.batches_done) + 1
            if len(bad_idx) == 0:
                # the batch crashed once but every probe passed (a
                # transient riding the poison window): retry it whole
                raise
            dead_letter.put_rows(
                _subset_cols(cols, bad_idx), reason="crash",
                errors=[f"{type(errors[int(i)]).__name__}: "
                        f"{errors[int(i)]}"[:300] for i in bad_idx],
                batch_index=batch_index, offsets=offsets)
            quarantined += len(bad_idx)
            log.warning(
                "poison isolation: batch %d crashed (%s: %s); "
                "quarantined %d/%d rows to the dead-letter queue",
                batch_index, type(e).__name__, str(e)[:120],
                len(bad_idx), len(next(iter(cols.values()))))
            good = np.ones(len(next(iter(cols.values()))), dtype=bool)
            good[bad_idx] = False
            if good.any():
                # survivors score from the pre-batch snapshot — feature
                # state never sees the quarantined rows
                res = engine.process_batch(_subset_cols(
                    cols, np.flatnonzero(good)))
            else:
                engine.state.batches_done += 1
                res = empty_batch_result(engine.state.batches_done)
            engine.state.offsets = offsets
            if sink is not None:
                sink.append(res)
            break  # checkpoint below advances PAST the poison batch
        engine.state.offsets = offsets
        if sink is not None:
            sink.append(res)
        clean += 1
    drain = getattr(sink, "drain", None) if sink is not None else None
    if drain is not None:
        drain()
    checkpointer.save(engine.checkpoint_state())
    commit = getattr(source, "commit", None)
    if commit is not None:
        commit()
    if rec is not None:
        rec.record_event("poison", phase="isolated", rows=quarantined,
                         batches_done=int(engine.state.batches_done))
    return quarantined


def run_with_recovery(
    make_engine: Callable[[], object],
    source=None,
    checkpointer=None,
    sink=None,
    max_restarts: int = 3,
    max_batches: int = 0,
    heartbeat: Optional[Heartbeat] = None,
    stall_timeout_s: float = 0.0,
    resume: bool = True,
    make_source: Optional[Callable[[], object]] = None,
    make_feedback: Optional[Callable[[object], object]] = None,
    make_model_reload: Optional[Callable[[], object]] = None,
    learning=None,
    recover_on: Tuple[Type[BaseException], ...] = (
        TransientError, OSError, ConnectionError,
    ),
    crash_loop_k: int = 2,
    dead_letter=None,
    restart_backoff: Optional[RetryPolicy] = None,
    sleep: Callable[[float], None] = time.sleep,
) -> dict:
    """Supervisor loop: run → on crash OR stall, restore checkpoint, resume.

    ``make_engine`` builds a fresh engine (state template) per incarnation;
    the checkpointer restores (offsets, feature state, params, scaler) into
    it and the source seeks to the checkpointed offsets, so every committed
    micro-batch is processed exactly once and uncommitted ones are replayed
    — Spark's checkpointLocation recovery contract (SURVEY §5.4).

    Stall watchdog: pass ``stall_timeout_s`` (or a pre-built ``heartbeat``)
    and each incarnation runs in a worker thread beating the heartbeat per
    loop pass while the supervisor watches ``healthy()`` — a silently hung
    source or device step (the failure retries can't see: no exception is
    ever raised) is detected within the stall budget and recovered like a
    crash. Without either, the loop is synchronous and reacts to
    exceptions only.

    ``make_feedback``: factory called with each incarnation's engine to
    build its labeled-feedback loop (a fresh consumer session per
    incarnation in production — see :class:`~.feedback.KafkaFeedbackSource`).

    ``make_source``: factory for a FRESH source per incarnation (the
    restart re-seeks it to the checkpointed offsets). Strongly preferred
    with the watchdog: an abandoned incarnation then owns a dead private
    session and can never touch the live stream — the analogue of Kafka's
    consumer-group generation fencing. With a single shared ``source``,
    the fence still blocks a zombie's future accesses, but a poll that
    was in flight at abandonment and later returns has already consumed
    its rows: that batch is dropped (at-most-one-batch loss in a rare
    double-fault race). At least one of ``source``/``make_source`` is
    required.

    The sink must tolerate replayed batches (idempotent append by tx_id or
    latest-wins MERGE downstream, as in the reference's MERGE INTO).

    ``resume=False`` ignores any pre-existing checkpoint for the whole run
    (a fresh pass over the stream): the checkpointer is fenced so crash
    incarnations restore only checkpoints written by THIS run — a stale
    checkpoint from a previous run is never silently resumed, even if the
    first incarnation crashes before its first save. ``recover_on`` lists
    the exception types treated as recoverable; anything else propagates
    immediately (engine bugs should crash loudly, not restart-loop).

    **Crash-loop breaker**: ``crash_loop_k`` consecutive same-typed
    crash failures at the SAME progress point (the engine's batch
    counter + offsets at failure time, so progress a dying incarnation
    made before crashing resets the streak) reclassify the failure from
    transient to poison
    (``rtfds_crash_loops_total``, flight-record ``poison`` event) instead
    of burning the restart budget on a deterministic replay. With a
    ``dead_letter`` sink (:class:`~..io.sink.DeadLetterSink`) the next
    incarnation runs :func:`_run_poison_isolation`: the offending
    micro-batch is replayed through the engine in halves against a
    pre-batch state snapshot down to the minimal failing row set, those
    rows are quarantined (idempotent by tx_id, so a crash mid-bisection
    neither loses nor duplicates them), survivors are scored and sunk
    normally, and the stream continues with offsets advanced — at-most-K
    restarts per poison batch, never stream death (the restart budget is
    refunded on successful isolation). Without a dead-letter sink the
    breaker logs the diagnosis + fires ``rtfds_crash_loops_total`` once
    per loop but keeps the budgeted, backed-off retry — a same-point
    transient (e.g. a broker outage) must not die earlier than it would
    have before the breaker existed, and a true poison loop is still
    bounded by ``max_restarts`` exactly as before.
    Stall-caused restarts never count toward the crash streak.

    **Restart backoff**: ``restart_backoff`` (a :class:`RetryPolicy`)
    sleeps between crash-caused restarts — exponential with optional
    full jitter, metered as ``rtfds_restart_backoff_seconds_total``.
    Stall-caused restarts skip it (they already waited out the stall
    budget). ``None`` (default) keeps the legacy hot restart loop.
    """
    if source is None and make_source is None:
        raise ValueError("run_with_recovery needs a source or make_source")
    restarts = 0
    budget_used = 0  # like restarts, but refunded on poison isolation
    fail_key: Optional[tuple] = None  # resume point of the last crash
    fail_count = 0  # consecutive crashes at fail_key
    poison_pending = False
    if source is None:
        source = make_source()
    initial_offsets = list(source.offsets)
    if not resume:
        checkpointer = _FencedCheckpointer(checkpointer)
    if heartbeat is None and stall_timeout_s > 0:
        heartbeat = Heartbeat(timeout_s=stall_timeout_s)
    last_was_stall = False
    t_session = time.monotonic()
    while True:
        engine = make_engine()
        if restarts > 0 and make_source is not None:
            # Fresh source session per incarnation: the previous (possibly
            # zombie) session is cut loose. Closed best-effort only after a
            # CRASH — after a stall the zombie thread may still be blocked
            # inside it and close() could hang the supervisor too.
            close = getattr(source, "close", None)
            if close is not None and not last_was_stall:
                try:
                    close()
                # rtfdslint: disable=exception-swallow (best-effort close of a DEAD incarnation's source; the real crash is already being handled by the supervisor — a close error here must not mask it)
                except Exception:  # a dying session may not close cleanly
                    pass
            source = make_source()
        restored = None
        if resume or restarts > 0:
            # With resume=False the fence makes this a no-op until the
            # current run has saved at least once.
            restored = checkpointer.restore(engine.state)
        if restored is not None:
            source.seek(engine.state.offsets)
            log.info("restored checkpoint at batch %d",
                     engine.state.batches_done)
        else:
            # No checkpoint yet: a fresh engine must consume from the very
            # beginning, or batches polled before the crash would be lost
            # to the new (empty) feature state.
            source.seek(initial_offsets)
        # Sink-side restore fence: drop indexed output parts beyond the
        # restored batch counter (0 on a fresh start) — replay may
        # re-batch the backlog differently, leaving stale parts it never
        # overwrites (the sink analogue of the checkpoint fence above).
        truncate = getattr(sink, "truncate_after", None) if sink else None
        if truncate is not None:
            truncate(engine.state.batches_done)
        # Feedback loop binds THIS incarnation's engine (and, in
        # production, its own consumer session). Isolation incarnations
        # run without feedback/reload — they exist to diagnose one batch.
        feedback = (make_feedback(engine)
                    if make_feedback and not poison_pending else None)
        # A FRESH reloader per incarnation: the restored checkpoint holds
        # pre-swap weights, so the new incarnation must re-apply the
        # latest artifact on its first interval instead of trusting a
        # previous incarnation's signature — and an abandoned (zombie)
        # worker keeps only ITS closure, never mutating the live one's.
        model_reload = (make_model_reload()
                        if make_model_reload and not poison_pending
                        else None)
        # Isolation must run UNPREFETCHED: a PrefetchSource's producer
        # thread polling ahead during bisection would decouple the
        # polled position from the batch under diagnosis. set_sync(True)
        # stops the producer and rewinds the inner source to the
        # consumed position, so isolation sees the same batch boundaries
        # a checkpoint replay would; flipped back after isolation.
        set_sync = getattr(source, "set_sync", None)
        if poison_pending and set_sync is not None:
            set_sync(True)
        try:
            if poison_pending:
                # No training overlaps a bisection in progress: the
                # learner's device work would race the unpipelined
                # probe steps' timing diagnosis.
                if learning is not None:
                    learning.pause()
                if heartbeat is not None:
                    # Isolation under the same stall watchdog + zombie
                    # fencing as a normal incarnation: a batch that HANGS
                    # mid-diagnosis is bounded by the stall budget too.
                    _run_watched(
                        engine, source, sink, checkpointer, max_batches,
                        heartbeat,
                        target=lambda src, snk, ckpt, hb:
                        _run_poison_isolation(
                            engine, src, snk, ckpt, dead_letter,
                            max_batches, recover_on, heartbeat=hb),
                    )
                else:
                    _run_poison_isolation(
                        engine, source, sink, checkpointer, dead_letter,
                        max_batches, recover_on,
                    )
                # Progress was made past the suspect point: clear the
                # diagnosis and REFUND the restart budget the crash loop
                # consumed — a poison batch must never kill the stream.
                poison_pending = False
                fail_key, fail_count = None, 0
                budget_used = 0
                if set_sync is not None:
                    set_sync(False)  # fast (prefetched) mode resumes
                if learning is not None:
                    learning.resume()
                continue
            if heartbeat is not None:
                stats = _run_watched(
                    engine, source, sink, checkpointer, max_batches,
                    heartbeat, feedback=feedback, model_reload=model_reload,
                    learning=learning,
                )
            else:
                stats = engine.run(
                    source, sink=sink, checkpointer=checkpointer,
                    max_batches=max_batches, feedback=feedback,
                    model_reload=model_reload, learning=learning,
                )
            # Final checkpoint so a clean exit never replays. The
            # checkpoint VIEW (not raw state): with a terminal-sketch
            # exchange armed it strips adopted peer content so resize
            # merges sum disjoint per-process partials exactly.
            checkpointer.save(engine.checkpoint_state())
            commit = getattr(source, "commit", None)
            if commit is not None:
                commit()
            if feedback is not None:
                feedback.commit()
                feedback.close()
            stats["restarts"] = restarts
            # Whole-session totals: engine.run reports per-run deltas, but
            # a recovered session's caller wants rows across restarts —
            # the engine's lifetime counters (checkpoint-restored + this
            # incarnation) are exactly that. wall_s/rows_per_s are made
            # consistent with them: session wall clock, not the last
            # incarnation's.
            stats["rows"] = engine.state.rows_done
            stats["batches"] = engine.state.batches_done
            stats["wall_s"] = time.monotonic() - t_session
            stats["rows_per_s"] = (
                stats["rows"] / stats["wall_s"] if stats["wall_s"] > 0
                else 0.0
            )
            return stats
        except recover_on as e:
            restarts += 1
            budget_used += 1
            last_was_stall = isinstance(e, StallError)
            if feedback is not None and not last_was_stall:
                # Close the dead incarnation's feedback session so the
                # group rebalances promptly (a stalled zombie may still
                # be inside it — leak that one rather than hang here).
                try:
                    feedback.close()
                # rtfdslint: disable=exception-swallow (best-effort close of the dead incarnation's feedback session so the group rebalances; the crash being recovered is the signal, not this close)
                except Exception:
                    pass
            log.warning("engine crashed (%s); restart %d/%d",
                        e, restarts, max_restarts)
            cause = "stall" if last_was_stall else "crash"
            err_s = f"{type(e).__name__}: {e}"[:200]
            rec = active_recorder()
            classified = False
            if not last_was_stall and not poison_pending:
                # Crash-loop breaker: consecutive same-typed crashes at
                # the SAME progress point (the engine's batch counter +
                # offsets AT failure — progress made by the dying
                # incarnation counts, checkpointed or not) are a
                # deterministic replay, not bad luck.
                fail_sig = (
                    int(getattr(engine.state, "batches_done", -1)),
                    tuple(int(x) for x in
                          getattr(engine.state, "offsets", ()) or ()),
                    type(e).__name__,
                )
                if fail_sig == fail_key:
                    fail_count += 1
                else:
                    fail_key, fail_count = fail_sig, 1
                if fail_count == max(1, int(crash_loop_k)):
                    # first crossing of K: the failure is now diagnosed
                    # as poison (the metric/event fire ONCE per loop)
                    get_registry().counter(
                        "rtfds_crash_loops_total",
                        "crash loops reclassified from transient to "
                        "poison (K consecutive failures at one progress "
                        "point)").inc()
                    if rec is not None:
                        rec.record_event(
                            "poison", phase="detected",
                            resume_batch=fail_key[0],
                            failures=fail_count, error=err_s)
                    if dead_letter is None:
                        # No quarantine path configured: log the
                        # diagnosis but keep the budgeted (backed-off)
                        # retry — a same-point transient (broker outage)
                        # must not die earlier than it would have before
                        # the breaker existed; the budget bounds a true
                        # poison loop exactly as before.
                        log.error(
                            "crash loop: %d consecutive failures at "
                            "progress point %s — likely poison input; "
                            "configure a dead-letter sink "
                            "(--dead-letter) to quarantine it instead "
                            "of retrying into the restart budget",
                            fail_count, fail_key)
                    else:
                        classified = True
            if classified:
                # The classification restart rides the normal restart
                # telemetry but skips the budget check: poison handling
                # is bounded by construction (isolation either advances
                # past the batch or its own failures land back here with
                # poison_pending set, where the budget DOES apply).
                poison_pending = True
                fail_key, fail_count = None, 0
            elif budget_used > max_restarts:
                # budget exhausted: the final failure is NOT a restart —
                # counting it would skew the baseline chaos PRs assert on
                if rec is not None:
                    rec.record_event(
                        "gave_up", restarts=restarts - 1, cause=cause,
                        error=err_s)
                raise
            get_registry().counter(
                "rtfds_engine_restarts_total",
                "supervisor restarts by cause", cause=cause).inc()
            if rec is not None:
                rec.record_event(
                    "restart", restarts=restarts, cause=cause, error=err_s)
            if restart_backoff is not None and not last_was_stall \
                    and not classified:
                # Exponential backoff + jitter between restarts — crash
                # restarts AND failed-isolation retries (a down broker
                # mid-diagnosis must not hot-loop); skipped for stalls
                # (they already waited out the stall budget) and for the
                # classification transition itself (diagnosis should
                # start immediately).
                d = restart_backoff.sleep_s(budget_used - 1)
                if d > 0:
                    get_registry().counter(
                        "rtfds_restart_backoff_seconds_total",
                        "seconds slept backing off between restarts",
                    ).inc(d)
                    log.info("backing off %.2fs before restart %d",
                             d, restarts)
                    sleep(d)
