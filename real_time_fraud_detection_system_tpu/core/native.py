"""ctypes loader for the C++ envelope decoder (``native/envelope.cc``).

Compiles the shared library on first use (g++ available in the image; the
build is one translation unit, <1 s) and caches the handle. All callers go
through :func:`decode_transaction_envelopes_native`, which has the same
interface as the pure-Python
:func:`..core.envelope.decode_transaction_envelopes` — the dispatcher there
prefers this path when available.

Validity contract (differential-fuzz-pinned, ``tests/test_native.py``):
the scanner extracts the required payload fields WITHOUT validating the
whole JSON document — that is what makes it line-rate. Consequently it is
strictly MORE lenient than the Python decoder: every message the scanner
rejects, the strict parser rejects too, and on messages both accept the
decoded columns are bit-identical; but a message whose required fields are
intact inside otherwise-broken JSON (truncated tail, garbage between
tokens) decodes here and is rejected by the strict parser. For
well-formed Debezium traffic the two are exactly equivalent. (The scanner
also does not un-escape ``\\uXXXX`` key names — Debezium never emits
them.)
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
import time
from typing import Iterable, List, Optional, Sequence, Tuple

import numpy as np


class NativeUnavailableError(RuntimeError):
    """The native .so could not be built/loaded in this process.

    A deploy/toolchain condition, not a data fault: callers gate via
    :func:`native_available` / :func:`hostprep_available`, so reaching
    this raise means a caller skipped the gate — fail fast with a type
    the supervisor taxonomy can tell apart from a jax-internal
    RuntimeError (subclasses RuntimeError for back-compat with any
    external catcher)."""


_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_build_error: Optional[str] = None


def _slab_hist():
    """Per-slab decode-time histogram. Resolved once per decode BATCH
    (one get-or-create under the registry lock, ~µs at batch
    granularity), not cached module-level: the process registry can be
    cleared between runs and a cached series would go orphan."""
    from real_time_fraud_detection_system_tpu.utils.metrics import (
        get_registry,
    )

    return get_registry().histogram(
        "rtfds_decode_slab_seconds",
        "wall time of one ingest-decode slab (a contiguous envelope "
        "range scanned by one worker)")


def _repo_root() -> str:
    return os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )


def _build_and_load(name: str, configure) -> "Tuple[Optional[ctypes.CDLL], Optional[str]]":
    """Shared compile-on-first-use recipe for every native unit:
    recompile when the source is newer than the .so, load via ctypes,
    hand the handle to ``configure(lib)`` for argtype setup, and report
    (lib, None) or (None, error). Caller holds ``_lock``."""
    src = os.path.join(_repo_root(), "native", f"{name}.cc")
    so = os.path.join(_repo_root(), "native", f"lib{name}.so")
    try:
        if not os.path.exists(so) or \
                os.path.getmtime(so) < os.path.getmtime(src):
            # rtfdslint: disable=blocking-call-on-loop-thread (one-time native build on first decode; .so is cached for the process/filesystem lifetime)
            subprocess.run(
                ["g++", "-O3", "-shared", "-fPIC", "-o", so, src],
                check=True, capture_output=True, text=True, timeout=120,
            )
        lib = ctypes.CDLL(so)
        configure(lib)
        return lib, None
    except (subprocess.CalledProcessError, OSError,
            subprocess.TimeoutExpired) as exc:
        return None, str(exc)


def _configure_envelope(lib) -> None:
    out_cols = [
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
    ] * 5 + [
        np.ctypeslib.ndpointer(np.int8, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS"),
    ]
    lib.decode_envelopes.restype = ctypes.c_int64
    lib.decode_envelopes.argtypes = [
        ctypes.c_char_p,
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        ctypes.c_int64,
    ] + out_cols


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _build_error
    with _lock:
        if _lib is None and _build_error is None:
            _lib, _build_error = _build_and_load(
                "envelope", _configure_envelope)
        return _lib


def native_available() -> bool:
    return _load() is not None


_pools: dict = {}  # worker count -> ThreadPoolExecutor
_AUTO_WORKERS = min(8, os.cpu_count() or 1)
_decode_workers = 0  # 0 = auto (_AUTO_WORKERS)
_PARALLEL_MIN = 8192  # below this, thread fan-out costs more than it saves


def set_decode_workers(n: int) -> int:
    """Set the process-wide ingest-decode worker count (0 = auto:
    min(8, cores); 1 = serial). Returns the resolved count. The pool is
    rebuilt lazily on the next decode, so this is safe to call between
    runs (the CLI calls it once at startup from --decode-workers)."""
    global _decode_workers
    n = max(0, int(n))
    with _lock:
        _decode_workers = n
    resolved = n or _AUTO_WORKERS
    from real_time_fraud_detection_system_tpu.utils.metrics import (
        get_registry,
    )

    get_registry().gauge(
        "rtfds_decode_workers",
        "configured ingest-decode worker threads").set(resolved)
    return resolved


def get_decode_workers() -> int:
    """The resolved decode worker count (auto applied)."""
    return _decode_workers or _AUTO_WORKERS


def _get_pool(workers: int):
    """Decode pool for ``workers``, one per distinct size. Never shut
    down on a size change: another thread (a prefetch producer, a
    concurrent bench variant) may be mid-``pool.map`` on the old pool,
    and a shutdown there raises into ITS in-flight decode. Distinct
    sizes in one process are a handful (explicit test/bench overrides +
    the configured serving count), so the idle-thread cost is bounded."""
    with _lock:
        pool = _pools.get(workers)
        if pool is None:
            from concurrent.futures import ThreadPoolExecutor

            pool = ThreadPoolExecutor(workers,
                                      thread_name_prefix="envelope-decode")
            _pools[workers] = pool
        return pool


def decode_envelopes_slab(
    buf: bytes,
    offsets: np.ndarray,
    a: int,
    b: int,
    tx_id: np.ndarray,
    t_us: np.ndarray,
    cust: np.ndarray,
    term: np.ndarray,
    cents: np.ndarray,
    op: np.ndarray,
    valid: np.ndarray,
) -> None:
    """Decode envelopes [a, b) of one packed byte-batch into rows [a, b)
    of the output columns — the per-worker unit of the parallel decode.
    ``offsets`` is the full absolute offset table (n+1 entries into
    ``buf``); each slab writes a disjoint slice of the shared columnar
    staging arrays, so concurrent slabs never contend. Public so tests
    can pin per-slab exactness against the whole-batch decode."""
    lib = _load()
    if lib is None:
        raise NativeUnavailableError(
            f"native decoder unavailable: {_build_error}")
    if b > a:
        lib.decode_envelopes(
            buf, offsets[a : b + 1], b - a,
            tx_id[a:b], t_us[a:b], cust[a:b], term[a:b], cents[a:b],
            op[a:b], valid[a:b],
        )


def decode_transaction_envelopes_native(
    messages: Iterable[bytes],
    kafka_timestamps_ms: Optional[Sequence[int]] = None,
    workers: Optional[int] = None,
) -> Tuple[dict, np.ndarray]:
    """Columnar decode via the C++ scanner. Same contract as the Python
    decoder; raises RuntimeError if the native library is unavailable.

    Large batches are sharded into contiguous offset slabs decoded
    concurrently over a thread pool (:func:`decode_envelopes_slab`): the
    ctypes call releases the GIL, the offset table is absolute into one
    shared packed buffer, and each slab writes a disjoint slice of the
    preallocated columnar staging arrays — the scan scales with cores
    (SURVEY's host-ingress hard part: 1M txns/s of JSON would bottleneck
    on a single-threaded parse before the TPU). ``workers`` overrides
    the process-wide :func:`set_decode_workers` setting for this call
    (1 = serial); per-slab wall time lands in
    ``rtfds_decode_slab_seconds``. The packed-buffer join beats a
    zero-copy pointer array here: building a ctypes ``c_char_p`` array
    costs ~2× the join (measured 108 ms vs 54 ms at 200k messages)."""
    lib = _load()
    if lib is None:
        raise NativeUnavailableError(
            f"native decoder unavailable: {_build_error}")
    msgs: List[bytes] = (
        messages if isinstance(messages, list) else list(messages)
    )
    n = len(msgs)
    offsets = np.zeros(n + 1, dtype=np.int64)
    if n:
        np.cumsum(
            np.fromiter((len(m) for m in msgs), dtype=np.int64, count=n),
            out=offsets[1:],
        )
    buf = b"".join(msgs)

    tx_id = np.zeros(n, dtype=np.int64)
    t_us = np.zeros(n, dtype=np.int64)
    cust = np.zeros(n, dtype=np.int64)
    term = np.zeros(n, dtype=np.int64)
    cents = np.zeros(n, dtype=np.int64)
    op = np.zeros(n, dtype=np.int8)
    valid = np.zeros(n, dtype=np.uint8)

    n_workers = max(1, int(workers) if workers else get_decode_workers())
    outs = (tx_id, t_us, cust, term, cents, op, valid)
    slab_hist = _slab_hist()

    def _scan(a: int, b: int) -> None:
        t0 = time.perf_counter()
        decode_envelopes_slab(buf, offsets, a, b, *outs)
        slab_hist.observe(time.perf_counter() - t0)

    if n >= _PARALLEL_MIN and n_workers > 1:
        bounds = np.linspace(0, n, n_workers + 1, dtype=np.int64)
        list(_get_pool(n_workers).map(
            lambda ab: _scan(int(ab[0]), int(ab[1])),
            zip(bounds[:-1], bounds[1:]),
        ))
    else:
        _scan(0, n)

    if kafka_timestamps_ms is None:
        kts = t_us // 1000
    else:
        kts = np.asarray(kafka_timestamps_ms, dtype=np.int64)
    cols = {
        "tx_id": tx_id,
        "tx_datetime_us": t_us,
        "customer_id": cust,
        "terminal_id": term,
        "tx_amount_cents": cents,
        "op": op,
        "kafka_ts_ms": kts,
    }
    return cols, valid == 0


# ---------------------------------------------------------------------------
# host-prep library (native/hostprep.cc): dedup + pack for the serving loop
# ---------------------------------------------------------------------------

_hp_lib: Optional[ctypes.CDLL] = None
_hp_error: Optional[str] = None


def _configure_hostprep(lib) -> None:
    i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
    lib.latest_wins_keep.restype = ctypes.c_int64
    lib.latest_wins_keep.argtypes = [
        i64p, i64p, ctypes.c_int64,
        np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS"),
    ]
    for fn in (lib.pack_rows, lib.pack_rows_wide):
        fn.restype = None
        fn.argtypes = [
            i64p, i64p, i64p, i64p,
            ctypes.c_void_p,  # label, nullable
            ctypes.c_int64, ctypes.c_int64,
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
        ]


def _load_hostprep() -> Optional[ctypes.CDLL]:
    global _hp_lib, _hp_error
    with _lock:
        if _hp_lib is None and _hp_error is None:
            _hp_lib, _hp_error = _build_and_load(
                "hostprep", _configure_hostprep)
        return _hp_lib


def hostprep_available() -> bool:
    return _load_hostprep() is not None


def latest_wins_keep(tx_id: np.ndarray, ts: np.ndarray) -> np.ndarray:
    """bool [n] latest-wins mask (same semantics as
    ops.dedup.latest_wins_mask_np with all rows valid), O(n) hash pass."""
    lib = _load_hostprep()
    if lib is None:
        raise NativeUnavailableError(
            f"native hostprep unavailable: {_hp_error}")
    n = len(tx_id)
    keep = np.zeros(n, dtype=np.uint8)
    if n:
        lib.latest_wins_keep(
            np.ascontiguousarray(tx_id, np.int64),
            np.ascontiguousarray(ts, np.int64), n, keep)
    return keep.view(bool)


def pack_rows(
    tx_datetime_us: np.ndarray,
    customer_id: np.ndarray,
    terminal_id: np.ndarray,
    amount_cents: np.ndarray,
    label: Optional[np.ndarray],
    pad: int,
    key_bits: int = 32,
) -> np.ndarray:
    """Fused make_batch + pack_batch: → int32 [7, pad] (zeros-padded;
    [9, pad] at ``key_bits=64``, the ids split and not folded),
    bit-identical to the NumPy composition (tests/test_native.py)."""
    lib = _load_hostprep()
    if lib is None:
        raise NativeUnavailableError(
            f"native hostprep unavailable: {_hp_error}")
    n = len(tx_datetime_us)
    if pad < n:
        raise ValueError(f"pad={pad} < batch rows {n}")
    wide = key_bits == 64
    packed = np.empty((9 if wide else 7, pad), dtype=np.int32)
    lab = (np.ascontiguousarray(label, np.int64)
           if label is not None else None)
    (lib.pack_rows_wide if wide else lib.pack_rows)(
        np.ascontiguousarray(tx_datetime_us, np.int64),
        np.ascontiguousarray(customer_id, np.int64),
        np.ascontiguousarray(terminal_id, np.int64),
        np.ascontiguousarray(amount_cents, np.int64),
        lab.ctypes.data if lab is not None else None,
        n, pad, packed)
    return packed
