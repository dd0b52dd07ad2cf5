"""Host cold tier for the exact feature store: demote, don't discard.

PR 13/14's recency compaction reclaimed hot-tier slots by *discarding*
the evicted keys' window rows — a returning key degraded to the CMS
sketch forever. This module is the third tier: an append+compact keyed
store on the host (local directory or object store), fed by the
compaction pass's demotion payload and read by the engine BEFORE the
step that scores a returning key's row (``runtime/engine.py::
_promote_returning``): exact per row, not eventually.

Everything follows the batch, not the key: the index is a pair of sorted
arrays per table, an append lands a payload with fancy-indexed copies,
a read gathers a batch's rows segment by segment. No per-key Python.

Layout: demoted rows buffer in memory and flush as immutable
**segments** — a blob ``seg-<seq>.npz`` (per-table ``{t}_keys`` u32 [n],
``{t}_bd`` i32 [n, NB], ``{t}_cnt/amt/frd`` f32 [n, NB]) written FIRST,
then a JSON **manifest** ``seg-<seq>.json`` as the commit point:
``{"format", "seq", "blob", "crc", "bytes", "keys": {table: [...]}}``.
Because the manifest embeds the key list, :meth:`ColdStore.open` (and
``rtfds ckpt --inspect``) rebuild the whole index from manifests alone —
no blob reads. A crash between blob and manifest leaves an orphan blob
(deleted on open); a torn manifest is quarantined, never trusted.

**Resident on the host:** every segment this process wrote or has read
stays in memory, as the arrays it was written from, until its last live
key is promoted (then the arrays go; the file waits for :meth:`gc`). At
most ``cold keys x (4 + 16 x n_day_buckets)`` bytes plus the rows
promoted since their segment's last live key left. A segment found on
disk at open is read — and CRC-checked — on its first use.

**What no window can see is forgotten.** The index keeps, beside each
key, the newest event day its rows hold. A compaction pass hands the
store the day before which history is dead (``now_day - (delay_days +
max(windows))``, the pass's own cutoff) and :meth:`ColdStore.expire`
drops every key whose newest day is older: no query can see its rows, so
if it comes back it is admitted afresh, as a key reclaimed from the hot
tier is. What stays resident is then bounded by the keys touched inside
the horizon, not by every key ever demoted. A manifest carries one day a
table (``newest_day``: no key of the segment is newer), which is what a
reopened store knows of its keys — they expire late, never early; a
manifest without it never expires.

Contracts, in the order people get them wrong:

- **Newest wins.** A key demoted twice resolves to its newest rows.
- **Reads verify.** A segment blob is CRC-checked when it is loaded from
  the backend. A mismatch quarantines the segment, drops its index
  entries and raises :class:`ColdStoreCorruptError` — the engine counts
  the lost keys (``exactness_degraded_keys``) and they are served from
  the sketch *honestly* instead of promoting garbage into the exact tier.
- **Checkpoint lineage = live segments.** :meth:`lineage` lists only
  segments that still hold un-promoted keys; :meth:`sync_to` (restore)
  deletes everything else — segments written after the checkpoint are
  regenerated by replay (exactly-once), fully-promoted ones are covered
  by the checkpointed hot state.
- **A store has one key width.** ``key_bits=32`` (the default) is the
  layout above, as it always was. ``key_bits=64`` holds ``uint64`` keys —
  the index, the ``{t}_keys`` arrays and the manifests' key lists carry
  the whole id, padding lanes are ``0xFFFFFFFF_FFFFFFFF`` — and stamps
  ``"key_bits": 64`` into every manifest; a manifest of the other width
  is refused at open by name (:class:`ColdStoreKeyWidthError`), never
  read as if it were this one's.
- **A fresh store per process**: ``tmp://[name]`` (see
  :func:`resolve_location`) is a directory made under the system's
  temporary directory at construction and removed at :meth:`close` or at
  interpreter exit. It cannot carry a checkpoint's lineage across a
  restart, and ``engine.run`` refuses a checkpointer with it.
"""

from __future__ import annotations

import io as _io
import json
import os
import queue
import shutil
import tempfile
import threading
import weakref
import zlib
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

SEG_FORMAT = 1
TMP_SCHEME = "tmp://"
_BUF = -1  # segment id of rows still in the flush buffer
_NO_DAY = np.iinfo(np.int32).max  # newest day unknown: never expires
_ROW_DTYPES = (np.int32, np.float32, np.float32, np.float32)


class ColdStoreCorruptError(Exception):
    """A cold segment failed CRC/parse verification (quarantined)."""


class ColdStoreKeyWidthError(ValueError):
    """The store on disk was written at another ``key_bits``."""


def _seg_blob(seq: int) -> str:
    return f"seg-{seq:08d}.npz"


def _seg_manifest(seq: int) -> str:
    return f"seg-{seq:08d}.json"


def resolve_location(path_or_url: str) -> Tuple[str, bool]:
    """``--cold-store`` as given → ``(where the store lives, ephemeral)``.

    ``tmp://[name]`` is the fresh-store form: a new directory
    ``<tempfile.gettempdir()>/<name or rtfds-cold>-<random>`` (so under
    ``TMPDIR``, beside a local sink's own temporary files), empty by
    construction and private to this process. Every other value is a
    durable location, used as it stands (a directory or ``s3://``)."""
    if not path_or_url.startswith(TMP_SCHEME):
        return path_or_url, False
    name = path_or_url[len(TMP_SCHEME):].strip("/") or "rtfds-cold"
    if os.sep in name or name in (".", ".."):
        raise ValueError(
            f"cold_store {path_or_url!r}: tmp:// takes a bare name, the "
            "directory is made under the system's temporary directory")
    return tempfile.mkdtemp(prefix=name + "-"), True


class _TableIndex:
    """One table's membership: ``keys`` sorted, and beside each key the
    segment (``_BUF`` = the flush buffer) and the row that hold its
    newest demoted rows, and the newest event day those rows hold."""

    __slots__ = ("keys", "seg", "row", "day")

    def __init__(self, key_dtype=np.uint32):
        self.keys = np.empty(0, key_dtype)
        self.seg = np.empty(0, np.int64)
        self.row = np.empty(0, np.int64)
        self.day = np.empty(0, np.int32)

    def find(self, keys: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """→ (position of each key, whether it is there)."""
        if not self.keys.size:
            return (np.zeros(keys.shape, np.int64),
                    np.zeros(keys.shape, bool))
        pos = np.searchsorted(self.keys, keys)
        pos_c = np.minimum(pos, self.keys.size - 1)
        return pos_c, self.keys[pos_c] == keys

    def put(self, keys: np.ndarray, seg: int, rows: np.ndarray,
            days: np.ndarray) -> np.ndarray:
        """``keys`` (unique) now live at ``(seg, rows)``, their newest
        event days ``days``: newest wins.
        → the segments that the keys already stored were in before."""
        pos, hit = self.find(keys)
        left = self.seg[pos[hit]]
        self.seg[pos[hit]] = seg
        self.row[pos[hit]] = rows[hit]
        self.day[pos[hit]] = days[hit]
        if hit.all():
            return left
        new_k = keys[~hit]
        order = np.argsort(new_k, kind="stable")
        new_k, new_r = new_k[order], rows[~hit][order]
        at = np.searchsorted(self.keys, new_k)
        self.keys = np.insert(self.keys, at, new_k)
        self.seg = np.insert(self.seg, at, seg)
        self.row = np.insert(self.row, at, new_r)
        self.day = np.insert(self.day, at, days[~hit][order])
        return left

    def keep(self, mask: np.ndarray) -> np.ndarray:
        """Keep ``mask``'s entries → the segments of those dropped."""
        gone = self.seg[~mask]
        self.keys, self.seg, self.row, self.day = (
            self.keys[mask], self.seg[mask], self.row[mask], self.day[mask])
        return gone


class ColdStore:
    """Append+compact keyed store over the checkpoint storage backends
    (``_LocalBackend``/``_StoreBackend`` — flaky-store retries and the
    atomic tmp+rename / atomic-PUT write discipline are inherited)."""

    def __init__(self, path_or_url: str, segment_mb: float = 4.0,
                 key_bits: int = 32):
        from real_time_fraud_detection_system_tpu.io.checkpoint import (
            _LocalBackend,
            _StoreBackend,
        )

        if key_bits not in (32, 64):
            raise ValueError(f"key_bits must be 32 or 64, got {key_bits!r}")
        self.key_bits = int(key_bits)
        self._key_dtype = np.uint64 if key_bits == 64 else np.uint32
        self._empty_key = np.iinfo(self._key_dtype).max  # a padding lane
        self.url = path_or_url
        self.path, self.ephemeral = resolve_location(path_or_url)
        self._cleanup = None
        if self.ephemeral:
            # gone with the store, or with the process if nobody closes it
            self._cleanup = weakref.finalize(
                self, shutil.rmtree, self.path, ignore_errors=True)
        if self.path.startswith("s3://"):
            from real_time_fraud_detection_system_tpu.io.store import (
                make_store,
            )

            self._backend = _StoreBackend(make_store(self.path), prefix="")
        else:
            self._backend = _LocalBackend(self.path)
        self._segment_bytes = max(1, int(segment_mb * (1 << 20)))
        # _lock guards the index and the resident arrays; _io_lock
        # serialises segment writes, which run outside _lock so that a
        # large flush never holds up the loop thread's detection.
        self._lock = threading.RLock()
        self._io_lock = threading.Lock()
        self._tables: Dict[str, _TableIndex] = {}
        self._seg_meta: Dict[int, dict] = {}  # seq -> committed manifest
        self._data: Dict[int, dict] = {}  # seq/_BUF -> {table: 4 arrays}
        self._live: Dict[int, int] = {}  # seq/_BUF -> keys it is newest for
        self._buf_bytes = 0
        self._next_seq = 0
        self._open()

    # -- open / close / crash hygiene -----------------------------------

    def _canon_keys(self, keys) -> np.ndarray:
        return np.asarray(keys, dtype=self._key_dtype).reshape(-1)

    def _log(self):
        from real_time_fraud_detection_system_tpu.utils import get_logger

        return get_logger("coldstore")

    def _open(self) -> None:
        """Rebuild the key index from segment MANIFESTS alone (no blob
        reads). Torn manifests are quarantined; orphan blobs (crash
        between blob write and manifest commit) and orphan tmps are
        deleted — they are by definition uncommitted."""
        names = self._backend.list_names()
        for n in names:
            if n.startswith("seg-") and n.endswith(".tmp"):
                self._backend.delete(n)
        blobs = {n for n in names
                 if n.startswith("seg-") and n.endswith(".npz")}
        for n in sorted(names):
            if not (n.startswith("seg-") and n.endswith(".json")):
                continue
            try:
                man = json.loads(self._backend.read(n).decode("utf-8"))
                seq = int(man["seq"])
                blob = str(man["blob"])
                int(man["crc"])
                bits = int(man.get("key_bits", 32))
                keys = {t: np.asarray(ks, dtype=self._key_dtype)
                        for t, ks in dict(man["keys"]).items()
                        } if bits == self.key_bits else None
            # rtfdslint: disable=broad-exception-catch (crash-safe re-open: ANY unparsable manifest is quarantined evidence, never trusted state)
            except Exception:
                self._quarantine_name(n)
                continue
            if keys is None:
                # a healthy segment of the other width: not evidence of
                # a crash, so nothing is quarantined — the store is not
                # this deployment's
                raise ColdStoreKeyWidthError(
                    f"cold store {self.path}: segment {n} was written at "
                    f"key_bits={bits}, this deployment states "
                    f"key_bits={self.key_bits} — a folded key and a "
                    "whole one do not name the same rows; point "
                    "--cold-store at a fresh location or restore the "
                    "width the store was written at")
            if blob not in blobs:
                # manifest without its blob: a deleted/torn blob —
                # quarantine the manifest, keys degrade to CMS honestly
                self._quarantine_name(n)
                continue
            self._seg_meta[seq] = man
            newest = man.get("newest_day", {})
            for table, ks in keys.items():
                # a key listed twice in one manifest: the later row wins
                uk, first_rev = np.unique(ks[::-1], return_index=True)
                self._put(table, uk, seq,
                          (ks.size - 1 - first_rev).astype(np.int64),
                          np.full(uk.size, newest.get(table, _NO_DAY),
                                  np.int32))
            self._next_seq = max(self._next_seq, seq + 1)
        committed = {m["blob"] for m in self._seg_meta.values()}
        for b in blobs:
            if b not in committed:
                self._backend.delete(b)  # orphan blob: never committed

    def close(self) -> None:
        """An ephemeral (``tmp://``) store removes its directory; a
        durable one has nothing to release."""
        if self._cleanup is not None:
            self._cleanup()

    def _quarantine_name(self, name: str) -> None:
        try:
            self._backend.move(name, "quarantine-" + name)
        except (OSError, KeyError):
            self._backend.delete(name)

    def _table(self, table: str) -> _TableIndex:
        t = self._tables.get(table)
        if t is None:
            t = self._tables[table] = _TableIndex(self._key_dtype)
        return t

    def _left(self, segs: np.ndarray) -> None:
        """Index entries left ``segs`` (one element an entry): a segment
        no key points at any more gives up its resident arrays (the file
        waits for :meth:`gc`)."""
        for seq, n in zip(*np.unique(segs, return_counts=True)):
            seq = int(seq)
            self._live[seq] = self._live.get(seq, 0) - int(n)
            if self._live[seq] <= 0:
                del self._live[seq]
                if seq != _BUF:
                    self._data.pop(seq, None)

    def _put(self, table: str, keys: np.ndarray, seg: int,
             rows: np.ndarray, days: np.ndarray) -> None:
        self._live[seg] = self._live.get(seg, 0) + int(keys.size)
        self._left(self._table(table).put(keys, seg, rows, days))

    def _keep(self, table: str, mask: np.ndarray) -> int:
        if mask.all():
            return 0
        gone = self._table(table).keep(mask)
        self._left(gone)
        return int(gone.size)

    # -- append (compaction demotions) ----------------------------------

    def append(self, table: str, keys, bd, cnt, amt, frd,
               flush: bool = True, days=None) -> int:
        """Land demoted rows (``EMPTY_KEY`` lanes skipped; a key given
        twice keeps its last row): readable at once. A segment is
        flushed once the buffer crosses the segment threshold — here, or
        (``flush=False``) when the caller says :meth:`flush_if_full`.
        ``days`` [n] is the newest ``bucket_day`` of each lane where the
        caller has it already (:func:`newest_days`). Returns the rows
        appended."""
        keys = self._canon_keys(keys)
        live = np.flatnonzero(keys != self._empty_key)
        if not live.size:
            return 0
        rows = tuple(np.asarray(a, dtype=dt).reshape(keys.size, -1)
                     for a, dt in zip((bd, cnt, amt, frd), _ROW_DTYPES))
        uk = keys[:live.size]
        if live[-1] == live.size - 1 and bool((uk[1:] > uk[:-1]).all()):
            # the compaction pass's own payload: live lanes first, keys
            # ascending — landed as it stands; a view where most lanes
            # are live, a copy where it would pin mostly padding
            dense = 2 * live.size >= keys.size
            rows = tuple(a[:live.size] if dense else a[:live.size].copy()
                         for a in rows)
            take = slice(live.size)
        else:
            uk, first_rev = np.unique(keys[live][::-1], return_index=True)
            take = live[live.size - 1 - first_rev]  # last lane of each key
            rows = tuple(a[take] for a in rows)
        days = (newest_days(rows[0]) if days is None
                else np.asarray(days, np.int32).reshape(-1)[take])
        with self._lock:
            buf = self._data.setdefault(_BUF, {})
            old = buf.get(table)
            base = 0 if old is None else old[0].shape[0]
            buf[table] = rows if old is None else tuple(
                np.concatenate([o, r]) for o, r in zip(old, rows))
            self._put(table, uk, _BUF,
                      base + np.arange(uk.size, dtype=np.int64), days)
            self._buf_bytes += uk.size * (
                keys.itemsize + 16 * rows[0].shape[1])
        if flush:
            self.flush_if_full()
        return int(uk.size)

    def flush_if_full(self) -> Optional[int]:
        with self._lock:
            full = self._buf_bytes >= self._segment_bytes
        return self.flush() if full else None

    def flush(self) -> Optional[int]:
        """Flush buffered rows as a committed segment (checkpoint save
        calls this so the lineage it records is durable). Returns the
        new segment's seq, or None when the buffer was empty."""
        with self._io_lock:
            with self._lock:
                buf = self._data.pop(_BUF, None)
                self._buf_bytes = 0
                arrays, man_keys, resident, where, newest = (
                    {}, {}, {}, {}, {})
                for table, rows in sorted((buf or {}).items()):
                    t = self._table(table)
                    at = np.flatnonzero(t.seg == _BUF)
                    if not at.size:
                        continue  # every buffered row superseded/promoted
                    src = t.row[at]
                    # one payload, nothing of it gone since: as it stands
                    whole = (src.size == rows[0].shape[0]
                             and bool((np.diff(src) == 1).all()))
                    resident[table] = rows if whole else tuple(
                        a[src] for a in rows)
                    man_keys[table] = t.keys[at]
                    newest[table] = int(t.day[at].max())
                    where[table] = at
                    arrays[f"{table}_keys"] = man_keys[table]
                    for name, a in zip(("bd", "cnt", "amt", "frd"),
                                       resident[table]):
                        arrays[f"{table}_{name}"] = a
                if not man_keys:
                    self._live.pop(_BUF, None)
                    return None
                seq = self._next_seq
                self._next_seq = seq + 1
                # readable from memory at once; durable once the manifest
                # below is committed (lineage lists committed segments)
                self._data[seq] = resident
                self._live[seq] = self._live.pop(_BUF)
                for table, at in where.items():
                    t = self._table(table)
                    t.seg[at] = seq
                    t.row[at] = np.arange(at.size, dtype=np.int64)
            bio = _io.BytesIO()
            np.savez(bio, **arrays)
            data = bio.getvalue()
            man = {
                "format": SEG_FORMAT,
                "seq": seq,
                "blob": _seg_blob(seq),
                "crc": zlib.crc32(data) & 0xFFFFFFFF,
                "bytes": len(data),
                "keys": {t: ks.tolist() for t, ks in man_keys.items()},
                "newest_day": newest,
            }
            if self.key_bits == 64:
                man["key_bits"] = 64  # a 32-bit manifest stays as it was
            # Blob FIRST, manifest as the commit point: a crash between
            # the two leaves an orphan blob that open() deletes.
            self._backend.write(_seg_blob(seq), data)
            self._backend.write(
                _seg_manifest(seq),
                json.dumps(man, sort_keys=True).encode("utf-8"))
            with self._lock:
                self._seg_meta[seq] = man
            return seq

    # -- reads -----------------------------------------------------------

    def _load_segment(self, seq: int) -> dict:
        """The segment's arrays, from memory or — CRC-checked — from the
        backend."""
        with self._lock:
            got = self._data.get(seq)
            man = self._seg_meta.get(seq)
        if got is not None:
            return got
        if man is None:
            raise ColdStoreCorruptError(f"segment {seq} unknown")
        try:
            data = self._backend.read(man["blob"])
        except KeyError:
            self._drop_segment(seq, quarantine=True)
            raise ColdStoreCorruptError(
                f"segment {seq} blob missing") from None
        if (zlib.crc32(data) & 0xFFFFFFFF) != int(man["crc"]):
            self._drop_segment(seq, quarantine=True)
            raise ColdStoreCorruptError(
                f"segment {seq} CRC mismatch (byte corruption)")
        try:
            with np.load(_io.BytesIO(data)) as z:
                got = {
                    t: tuple(z[f"{t}_{n}"]
                             for n in ("bd", "cnt", "amt", "frd"))
                    for t in man["keys"]}
        # rtfdslint: disable=broad-exception-catch (a CRC-valid but unparsable npz is still poison: quarantine + typed raise, never propagate a decode internals error)
        except Exception:
            self._drop_segment(seq, quarantine=True)
            raise ColdStoreCorruptError(
                f"segment {seq} blob unparsable") from None
        with self._lock:
            self._data[seq] = got
        return got

    def read_rows(self, table: str, keys) -> tuple:
        """The batch read: ``(found [n] bool, bd, cnt, amt, frd)`` —
        which of ``keys`` the store holds, and one row for each of THOSE,
        in ``keys``' order (``keys[found]`` names them). One gather per
        segment touched. A corrupt segment raises
        :class:`ColdStoreCorruptError` after quarantining itself."""
        keys = self._canon_keys(keys)
        with self._lock:
            t = self._table(table)
            pos, found = t.find(keys)
            pos = pos[found]
            seg, row = t.seg[pos], t.row[pos]
            nb = 0
            parts = []
            for s in np.unique(seg).tolist():
                arrays = self._load_segment(int(s))[table]
                nb = arrays[0].shape[1]
                at = np.flatnonzero(seg == s)
                parts.append((at, tuple(a[row[at]] for a in arrays)))
        out = tuple(np.empty((pos.size, nb), dt) for dt in _ROW_DTYPES)
        for at, rows in parts:
            for o, r in zip(out, rows):
                o[at] = r
        return (found,) + out

    def get_rows(self, table: str, keys: Sequence[int]) -> dict:
        """The per-key view of :meth:`read_rows`: ``{key: (bd, cnt, amt,
        frd)}``, missing keys simply absent."""
        keys = self._canon_keys(keys)
        found, bd, cnt, amt, frd = self.read_rows(table, keys)
        return {int(k): (bd[j], cnt[j], amt[j], frd[j])
                for j, k in enumerate(keys[found])}

    def cold_mask(self, table: str, keys) -> np.ndarray:
        """Which of ``keys`` (any order, repeats allowed) the store
        holds."""
        return self.find_days(table, keys)[0]

    def find_days(self, table: str, keys) -> Tuple[np.ndarray, np.ndarray]:
        """The engine's returning-key detection, one sorted-index lookup
        a batch: which of ``keys`` (any order, repeats allowed) the store
        holds, and beside it the newest event day of each (undefined
        where the store holds none) — what tells a returning key from
        one whose rows no window can see any more."""
        keys = self._canon_keys(keys)
        with self._lock:
            t = self._table(table)
            pos, found = t.find(keys)
            days = t.day[pos] if t.keys.size else np.zeros(
                keys.shape, np.int32)
            return found, days

    def contains(self, table: str, key: int) -> bool:
        return bool(self.cold_mask(table, [key])[0])

    def index_snapshot(self, table: str) -> np.ndarray:
        """Sorted array of this table's cold keys (a copy): ``uint32``,
        or ``uint64`` in a ``key_bits=64`` store."""
        with self._lock:
            return self._table(table).keys.copy()

    # -- promotion / retention ------------------------------------------

    def _live_segs(self) -> set:
        return {s for s in self._live if s != _BUF}

    def _forget(self, table: str, keys: np.ndarray) -> int:
        t = self._table(table)
        pos, hit = t.find(keys)
        mask = np.ones(t.keys.size, bool)
        mask[pos[hit]] = False
        return self._keep(table, mask)

    def mark_promoted(self, table: str, keys: Sequence[int]) -> None:
        """The keys' rows went back to the hot tier — or no window can
        see them any more and the key was admitted afresh: drop their
        index entries (stops re-detection; segment bytes stay until
        gc)."""
        with self._lock:
            self._forget(table, self._canon_keys(keys))

    def expire(self, cutoff_day: int) -> int:
        """The event-time horizon: drop every key whose newest event day
        is older than ``cutoff_day`` — the compaction pass's own cutoff,
        ``now_day - (delay_days + max(windows))``: history no query can
        see. A segment whose last live key goes gives up its resident
        arrays, as after a promotion. → the keys dropped."""
        gone = 0
        with self._lock:
            for table, t in self._tables.items():
                gone += self._keep(table, t.day >= cutoff_day)
        return gone

    def gc(self) -> List[int]:
        """Delete segments with zero live index entries. Call AFTER a
        checkpoint save: the just-recorded lineage lists only live
        segments, so nothing the newest checkpoint can need is removed
        (an *older* checkpoint restored over a gc'd store degrades those
        keys to CMS honestly, with a warning from :meth:`sync_to`)."""
        with self._lock:
            live = self._live_segs()
            dead = [s for s in self._seg_meta if s not in live]
            for seq in dead:
                self._drop_segment(seq, quarantine=False)
            return dead

    def _drop_segment(self, seq: int, quarantine: bool) -> None:
        with self._lock:
            man = self._seg_meta.pop(seq, None)
            for table, t in self._tables.items():
                self._keep(table, t.seg != seq)
            self._data.pop(seq, None)
            self._live.pop(seq, None)
        if man is None:
            return
        if quarantine:
            self._quarantine_name(man["blob"])
            self._quarantine_name(_seg_manifest(seq))
        else:
            self._backend.delete(man["blob"])
            self._backend.delete(_seg_manifest(seq))

    # -- checkpoint lineage ---------------------------------------------

    def lineage(self) -> dict:
        """LIVE-segment lineage for the checkpoint meta (call after
        :meth:`flush`). Per segment: seq/blob/crc/bytes + per-table key
        COUNTS (the full key lists live in the on-disk manifests)."""
        with self._lock:
            live = self._live_segs()
            segs = []
            for seq in sorted(self._seg_meta):
                if seq not in live:
                    continue
                man = self._seg_meta[seq]
                segs.append({
                    "seq": seq,
                    "blob": man["blob"],
                    "crc": int(man["crc"]),
                    "bytes": int(man["bytes"]),
                    "keys": {t: len(ks)
                             for t, ks in man["keys"].items()},
                })
            return {
                "cold_store": self.path,
                "segments": segs,
                "total_keys": self.keys_count,
                "total_bytes": sum(s["bytes"] for s in segs),
            }

    def sync_to(self, lineage: Optional[dict]) -> None:
        """Adopt a restored checkpoint's cold lineage: segments the
        checkpoint does not list are deleted (post-checkpoint demotions
        — replay regenerates them exactly-once; fully-promoted ones are
        covered by the checkpointed hot state), listed-but-missing
        segments warn (their keys serve from CMS honestly), and the
        flush buffer is dropped (its rows post-date the checkpoint)."""
        listed = {int(s["seq"]): s
                  for s in (lineage or {}).get("segments", [])}
        with self._io_lock, self._lock:
            for seq in [s for s in self._seg_meta if s not in listed]:
                self._drop_segment(seq, quarantine=False)
            for table, t in self._tables.items():
                self._keep(table, t.seg != _BUF)
            self._data.pop(_BUF, None)
            self._buf_bytes = 0
            missing = [s for s in listed if s not in self._seg_meta]
            top = max(list(listed) + list(self._seg_meta), default=-1)
            self._next_seq = max(self._next_seq, top + 1)
        if missing:
            self._log().warning(
                "cold-tier lineage lists segment(s) %s that are missing "
                "from %s — their keys will serve from the CMS sketch "
                "(exactness degraded, counted) instead of promoting",
                missing, self.path)

    def rehome(self, owned_fn) -> int:
        """Drop index entries for keys this process no longer owns after
        a topology change (fleet resize restore). ``owned_fn(table,
        keys_u32) -> bool mask`` is the NEW topology's ownership
        predicate; non-owned keys are unindexed here (their segment
        bytes stay until :meth:`gc`) — the owning process promotes them
        from ITS store. Returns the number of keys dropped."""
        dropped = 0
        with self._lock:
            for table in sorted(self._tables):
                ks = self._table(table).keys
                if not ks.size:
                    continue
                mine = np.asarray(owned_fn(table, ks), dtype=bool)
                dropped += self._forget(table, ks[~mine])
        return dropped

    # -- gauges ----------------------------------------------------------

    @property
    def keys_count(self) -> int:
        with self._lock:
            return sum(int(t.keys.size) for t in self._tables.values())

    @property
    def bytes(self) -> int:
        with self._lock:
            live = self._live_segs()
            seg = sum(int(m["bytes"]) for s, m in self._seg_meta.items()
                      if s in live)
            return seg + self._buf_bytes


def newest_days(bucket_day: np.ndarray) -> np.ndarray:
    """Newest event day of each window row: int32 [n] from ``bucket_day``
    [n, NB] (−1 for an empty row)."""
    return np.maximum.reduce(bucket_day, axis=1).astype(np.int32, copy=False)


def consolidate_cold_stores(src_paths: Sequence[str], dest_path: str,
                            segment_mb: float = 4.0) -> ColdStore:
    """Merge the LIVE rows of several per-process cold stores into one
    fresh store — the cold-tier half of a fleet-shrink merge. Sources
    are read via their committed indexes (newest row per key), appended
    into ``dest_path`` in deterministic per-table sorted-key order, and
    flushed; fleet ownership makes source key sets disjoint, and a
    corrupt source segment degrades exactly as it does at serve time
    (quarantined, its keys fall back to the CMS sketch) instead of
    failing the resize. Returns the opened destination store. (Fleets
    serve one-word keys: the stores are ``key_bits=32``.)"""
    dest = ColdStore(dest_path, segment_mb=segment_mb)
    for src_path in src_paths:
        if os.path.abspath(src_path) == os.path.abspath(dest_path):
            raise ValueError(
                f"cold consolidation destination {dest_path} is also a "
                "source — merge into a fresh generation directory")
        src = ColdStore(src_path, segment_mb=segment_mb)
        for table in sorted(src._tables):
            got = None
            # each corrupt segment quarantines itself on first touch, so
            # one extra attempt per segment always terminates
            for _ in range(len(src._seg_meta) + 1):
                ks = src.index_snapshot(table)
                try:
                    got = src.read_rows(table, ks)
                    break
                except ColdStoreCorruptError:
                    continue
            if got is None or not ks.size:
                continue
            dest.append(table, ks[got[0]], *got[1:])
    dest.flush()
    return dest


class SegmentWriter:
    """The cold tier's one writer thread: the durable copy, off the loop
    thread.

    A compaction pass's payload is landed by the loop thread itself,
    inside ``engine._maybe_compact`` (:meth:`ColdStore.append`: readable
    from memory at once, so a key that comes back in the very next batch
    is found where every other cold key is). What follows here, and what
    nobody waits for, is the segment write — the ``.npz`` blob, its CRC
    and the manifest of a full flush buffer. :meth:`kick` asks for one
    :meth:`ColdStore.flush_if_full` (the engine asks as it starts to
    wait for the next pass: the write takes the interpreter's lock from
    whoever else needs it); :meth:`wait` blocks until every
    request is served (a checkpoint save, the end of a run); a
    worker-side failure re-raises on the loop thread, with its own type,
    from the next call.
    """

    def __init__(self, store: ColdStore):
        self.store = store
        self._lock = threading.Lock()
        self._idle = threading.Condition(self._lock)
        self._inflight = 0
        self._error: Optional[BaseException] = None
        # rtfdslint: disable=unbounded-queue (one request a compaction pass, each a no-op unless the flush buffer is full: bounded by the passes a segment write outlasts)
        self._q: "queue.Queue" = queue.Queue()
        self._thread = threading.Thread(
            target=self._work, daemon=True, name="rtfds-cold-writer")
        self._thread.start()

    def _work(self) -> None:
        while self._q.get() is not None:
            try:
                self.store.flush_if_full()
            # rtfdslint: disable=broad-exception-catch (thread-boundary transport: the worker ships the ORIGINAL exception to the loop thread, which re-raises it typed from the next call)
            except BaseException as e:
                with self._lock:
                    self._error = self._error or e
            with self._lock:
                self._inflight -= 1
                self._idle.notify_all()

    def _raise(self) -> None:
        if self._error is not None:
            e, self._error = self._error, None
            raise e

    def kick(self) -> None:
        """Write a segment if the buffer is full."""
        with self._lock:
            self._raise()
            self._inflight += 1
        self._q.put(True)

    def wait(self) -> None:
        """Block until every requested write is done."""
        with self._lock:
            while self._inflight:
                self._idle.wait()
            self._raise()

    def close(self) -> None:
        self._q.put(None)
        self._thread.join(timeout=10.0)
