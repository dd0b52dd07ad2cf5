"""Verified, atomic checkpoint/resume of the full streaming state.

The reference's recovery story is Spark's ``checkpointLocation`` (Kafka
offsets + commit log per job, ``fraud_detection.py:63``) plus pickled model
artifacts. Here ONE checkpoint captures everything the step function closes
over — (source offsets, feature-state pytree, model params, scaler, batch
counter) — written atomically (tmp file + rename / atomic object PUT) so a
crash mid-write leaves the previous checkpoint intact. Restore rebuilds the
exact pytree structure from a template, so replay resumes with identical
state (exactly-once at micro-batch granularity: offsets and state are saved
together).

Format v2 — trust nothing on restore
------------------------------------
A v1 checkpoint was trusted blindly: a torn write, a bit-flip, or a flaky
GET either killed the stream or silently resurrected bad state. v2 embeds a
**verified manifest** next to the arrays (``__manifest__`` npz entry):

- a CRC32 per logical-state leaf (the npz arrays ``fs_i``/``p_i``/``s_i``);
- a **structural fingerprint** (sha256 over every leaf's key/shape/dtype —
  the materialized feature-spec + model-shape contract a restore template
  must match);
- the writer's **incarnation token** (which process wrote this lineage);
- for **delta** checkpoints: the base entry's name and the CRC32 of the
  base's manifest — the chain link that makes a delta restorable only
  against the exact object it was built from.

``restore()`` verifies checksums and structural compatibility and, on ANY
mismatch, quarantines the corrupt checkpoint (the same ``stale-…`` stash
the fresh-start fence uses) and **falls back down the lineage** to the
newest valid entry — ``rtfds_checkpoint_corrupt_total{reason=checksum|
truncated|incompatible}`` counts why, a ``checkpoint_fallback`` flight
event records what was skipped, and the supervisor replays from the older
fence instead of dying. v1 (pre-manifest) checkpoints still restore —
existing deployments upgrade in place.

Delta checkpoints — bounded save cost
-------------------------------------
With ``full_every=K > 1``, a full snapshot is written every K saves and the
saves between carry only the leaves whose bytes changed since the previous
save (params/scaler are static between hot-reloads; feature_state churns
every batch). Restore composes newest-valid-full + the verified delta
chain and re-checksums the COMPOSED state against the tip manifest, so a
delta restore is bit-identical to a full one or it is rejected; any broken
link falls back to the last valid full. ``rtfds_checkpoint_bytes{kind=
full|delta}`` meters the save-size win.

Flaky-store hardening
---------------------
``StoreCheckpointer`` ops (PUT/GET/LIST/DELETE/HEAD) run through
:func:`~..runtime.faults.with_retries` with original-typed error
propagation and an optional per-op timeout — a flaky S3 GET retries
instead of killing the stream, and a hung one surfaces as a transient
within the timeout instead of wedging the supervisor.
"""

from __future__ import annotations

import hashlib
import io as _io
import json
import os
import threading
import time
import uuid
import zipfile
import zlib
from typing import List, Optional, Tuple

import jax
import numpy as np

from real_time_fraud_detection_system_tpu.ops.windows import WindowState
from real_time_fraud_detection_system_tpu.utils.metrics import (
    active_recorder,
    get_registry,
)

CORRUPT_REASONS = ("checksum", "truncated", "incompatible")


class CorruptCheckpointError(Exception):
    """A checkpoint (or its delta chain) failed restore verification.

    ``reason`` is one of :data:`CORRUPT_REASONS`: ``checksum`` (bytes
    present but wrong — bit-flip, tampering, broken chain link),
    ``truncated`` (bytes missing/unreadable — torn write, partial PUT,
    missing base), ``incompatible`` (readable but structurally wrong for
    the restore template — config/feature-spec drift).
    """

    def __init__(self, reason: str, detail: str = ""):
        assert reason in CORRUPT_REASONS, reason
        self.reason = reason
        self.detail = detail
        super().__init__(f"{reason}: {detail}" if detail else reason)


class CheckpointKeyWidthError(ValueError):
    """The checkpoint is HEALTHY but was written at another
    ``features.key_bits`` than the restoring engine states: its key
    directories hold folded one-word keys where the template holds whole
    64-bit ones, or the reverse. Like a topology mismatch this is a
    refusal, never a quarantine — the whole lineage shares the width."""


def state_key_bits(feature_state) -> int:
    """The key width a feature state was built at: 64 where a key
    directory carries the entry's two key words, else 32."""
    dirs = (getattr(feature_state, name, None)
            for name in ("customer_dir", "terminal_dir"))
    return 64 if any(kd is not None and kd.wide for kd in dirs) else 32


class CheckpointTopologyError(ValueError):
    """The checkpoint is HEALTHY but was written under a different
    multi-host process topology than the restoring engine serves.

    Deliberately not a :class:`CorruptCheckpointError`: the lineage
    fallback quarantines corrupt entries and serves an older one, which
    for a topology mismatch would silently rewind a healthy fleet (every
    entry in the lineage has the same topology). Restore REFUSES
    instead, with the elastic-reshard fix in the message."""


def _observe_checkpoint(op: str, backend: str, t0: float, nbytes: int,
                        batches_done: int, kind: str = "full") -> None:
    """Shared save/restore instrumentation + the flight-record event a
    checkpoint IS (the exactly-once fence every replay reasons from)."""
    dt = time.perf_counter() - t0
    reg = get_registry()
    reg.histogram("rtfds_checkpoint_seconds",
                  "checkpoint save/restore wall time", op=op,
                  backend=backend).observe(dt)
    reg.counter("rtfds_checkpoint_ops_total", "checkpoint operations",
                op=op, backend=backend).inc()
    if nbytes:
        reg.gauge("rtfds_checkpoint_bytes",
                  "size of the last checkpoint").set(nbytes)
        reg.gauge("rtfds_checkpoint_bytes",
                  "size of the last checkpoint", kind=kind).set(nbytes)
    rec = active_recorder()
    if rec is not None:
        # NB: "kind" is the flight recorder's own record discriminator
        rec.record_event("checkpoint", op=op, batches_done=batches_done,
                         bytes=nbytes, seconds=round(dt, 6),
                         ckpt_kind=kind)


# ---------------------------------------------------------------------------
# State (de)serialization
# ---------------------------------------------------------------------------


def _leaves_on_disk(tree):
    """``(leaf, its on-disk shape)`` for every leaf of ``tree``, in pytree
    order. A window column is stored flat (``ops/windows.py``) but
    written as the ``[cap, NB]`` table it is — a free view of the host
    copy — so checkpoints taken before the flat layout restore under it
    and the reverse; every other leaf is written as it is."""
    def is_windows(x):
        return isinstance(x, WindowState)

    out = []
    for node in jax.tree_util.tree_leaves(tree, is_leaf=is_windows):
        if is_windows(node):
            out += [(c, (node.capacity, node.n_buckets))
                    for c in node.columns()]
        else:
            out.append((node, tuple(np.shape(node))))
    return out


def _state_arrays(engine_state) -> Tuple[dict, dict]:
    """Flatten an EngineState into the npz array dict + meta dict — the
    ONE place the on-disk leaf naming (``fs_i``/``p_i``/``s_i``) lives."""
    leaves_fs = _leaves_on_disk(engine_state.feature_state)
    leaves_p, _ = jax.tree_util.tree_flatten(engine_state.params)
    leaves_s, _ = jax.tree_util.tree_flatten(engine_state.scaler)
    arrays = {}
    for i, (leaf, shape) in enumerate(leaves_fs):
        arrays[f"fs_{i}"] = np.asarray(leaf).reshape(shape)
    for i, leaf in enumerate(leaves_p):
        arrays[f"p_{i}"] = np.asarray(leaf)
    for i, leaf in enumerate(leaves_s):
        arrays[f"s_{i}"] = np.asarray(leaf)
    meta = {
        "offsets": list(map(int, engine_state.offsets)),
        "batches_done": int(engine_state.batches_done),
        "rows_done": int(engine_state.rows_done),
        "n_fs": len(leaves_fs),
        "n_p": len(leaves_p),
        "n_s": len(leaves_s),
        # human/CLI leaf naming: fs_i -> pytree path, so `rtfds ckpt
        # --inspect` can attribute bytes to named state planes
        # (directories, tiers) without loading the arrays
        "fs_leaves": _fs_leaf_names(engine_state.feature_state),
        # layouts are shape-identical permutations: the writer's device
        # count must travel with the state for cross-width restores
        "layout_devices": int(
            getattr(engine_state, "layout_devices", 1) or 1),
        # multi-host: the writer's fleet topology. A per-process
        # checkpoint holds only its residue block's keys, so restore
        # refuses any topology change except the sanctioned 1→P
        # adoption (see Checkpointer._check_topology).
        "process_count": int(
            getattr(engine_state, "process_count", 1) or 1),
        "process_id": int(
            getattr(engine_state, "process_id", 0) or 0),
        # registry version the params descend from (None outside
        # continuous learning) — restore hands it back so the learning
        # loop can tell restored params from the current champion
        "model_version": getattr(engine_state, "model_version", None),
    }
    if state_key_bits(engine_state.feature_state) == 64:
        # the width the directories were written at; a 32-bit
        # checkpoint's meta is what it always was (absent = 32)
        meta["key_bits"] = 64
    occ = _directory_occupancy(engine_state.feature_state)
    if occ:
        # per-shard hot-tier occupancy at save time (tiered exact
        # store): the state-skew signal `rtfds ckpt --inspect` surfaces
        # from the manifest alone (shapes are static per shard — only
        # the VALUES betray skew, and free_top is one int per shard)
        meta["feature_state_occupancy"] = occ
    cl = getattr(engine_state, "cold_lineage", None)
    if cl:
        # cold-tier segment lineage (io/coldstore.py): which LIVE
        # segments this checkpoint's hot state pairs with. Restore hands
        # it to ColdStore.sync_to so post-checkpoint segments are pruned
        # (replay regenerates them — exactly-once across the tier
        # boundary) and `rtfds ckpt --inspect` surfaces the cold plane
        # from the manifest alone.
        meta["cold_lineage"] = cl
    re = getattr(engine_state, "resize_epochs", None)
    if re:
        # Elastic-fleet lineage: one record per fleet resize this state
        # has lived through (generation, from/to process counts, reason,
        # per-old-owner resume floors). `rtfds ckpt --inspect` surfaces
        # the resize history from the manifest alone, and a restored
        # worker re-derives its OwnershipFloorSource floors from the
        # newest record.
        meta["resize_epochs"] = re
    return arrays, meta


def _fs_leaf_names(feature_state) -> dict:
    """``fs_i`` → dotted pytree path of the feature-state leaf."""
    try:
        flat, _ = jax.tree_util.tree_flatten_with_path(feature_state)
        return {
            f"fs_{i}": jax.tree_util.keystr(path)
            for i, (path, _leaf) in enumerate(flat)
        }
    except (TypeError, AttributeError):  # exotic pytree: names optional
        return {}


def _directory_occupancy(feature_state) -> dict:
    """Per-table, per-shard occupied hot-tier slot counts (``{} `` when
    the state carries no key directories — direct/hash/sequence)."""
    out = {}
    for table in ("customer", "terminal"):
        kd = getattr(feature_state, f"{table}_dir", None)
        if kd is None:
            continue
        tops = np.asarray(kd.free_top)
        free = np.asarray(kd.free)
        if tops.ndim == 0:  # single-chip layout
            out[table] = [int(free.shape[0]) - int(tops)]
        else:  # stacked per-shard layout
            cap_local = int(free.shape[1])
            out[table] = [cap_local - int(t) for t in tops]
    return out


def _apply_arrays(engine_state, meta: dict, arrays: dict):
    """Rebuild an EngineState template from the (composed) array dict —
    the restore tail shared by v1 files and v2 full/delta chains."""
    # a leaf written through a view (a window table) goes back to the
    # shape the template holds it in; every other leaf keeps the
    # checkpoint's shape (a directory's follows the writer's device
    # count, and the engine's _ensure_layout re-homes it)
    fs_leaves = [
        a if np.shape(t) == on_disk else a.reshape(np.shape(t))
        for a, (t, on_disk) in zip(
            (arrays[f"fs_{i}"] for i in range(meta["n_fs"])),
            _leaves_on_disk(engine_state.feature_state))]
    p_leaves = [arrays[f"p_{i}"] for i in range(meta["n_p"])]
    s_leaves = [arrays[f"s_{i}"] for i in range(meta["n_s"])]
    _, fs_def = jax.tree_util.tree_flatten(engine_state.feature_state)
    _, p_def = jax.tree_util.tree_flatten(engine_state.params)
    _, s_def = jax.tree_util.tree_flatten(engine_state.scaler)
    engine_state.feature_state = jax.tree_util.tree_unflatten(
        fs_def, [jax.numpy.asarray(a) for a in fs_leaves]
    )
    engine_state.params = jax.tree_util.tree_unflatten(
        p_def, [jax.numpy.asarray(a) for a in p_leaves]
    )
    engine_state.scaler = jax.tree_util.tree_unflatten(
        s_def, [jax.numpy.asarray(a) for a in s_leaves]
    )
    engine_state.offsets = meta["offsets"]
    engine_state.batches_done = meta["batches_done"]
    engine_state.rows_done = meta["rows_done"]
    if meta.get("layout_devices") is not None:
        engine_state.layout_devices = int(meta["layout_devices"])
    # pre-layout-aware checkpoints: leave the template's value (the old
    # same-width-restore assumption)
    # Multi-host stamps reflect the WRITER (pre-multihost checkpoints
    # were single-process by construction, so the default is honest —
    # leaving a multi-process template's stamps would skip the 1→P
    # adoption the restored global state needs).
    engine_state.process_count = int(meta.get("process_count", 1) or 1)
    engine_state.process_id = int(meta.get("process_id", 0) or 0)
    if meta.get("model_version") is not None:
        engine_state.model_version = int(meta["model_version"])
    # pre-learning checkpoints carry no stamp: keep the template's value
    # (the version the fresh engine was built from), which makes a
    # champion-pointer mismatch err toward re-applying the champion
    if meta.get("cold_lineage") is not None:
        engine_state.cold_lineage = meta["cold_lineage"]
    if meta.get("resize_epochs") is not None:
        engine_state.resize_epochs = meta["resize_epochs"]
    return engine_state


def write_state_npz(fileobj, engine_state) -> None:
    """Stream an EngineState (or any object with feature_state/params/
    scaler/offsets/batches_done/rows_done) as npz into a file object.

    This is the RAW (v1-shaped) payload — no manifest — used for
    in-memory snapshots (poison-isolation probes) and object-store PUT
    bodies where the manifest is added by the checkpointer."""
    arrays, meta = _state_arrays(engine_state)
    np.savez(fileobj, __meta__=json.dumps(meta), **arrays)


def state_to_bytes(engine_state) -> bytes:
    """npz bytes of an EngineState (object-store PUT payload)."""
    buf = _io.BytesIO()
    write_state_npz(buf, engine_state)
    return buf.getvalue()


def bytes_to_state(data: bytes, engine_state):
    """Restore npz bytes into an EngineState template (same shapes);
    returns the mutated engine_state."""
    return read_state_npz(_io.BytesIO(data), engine_state)


def read_state_npz(fileobj, engine_state):
    """Restore npz from a file object into an EngineState template —
    streaming (np.load reads arrays directly; no whole-file bytes copy).
    No verification: this is the trusting raw reader (snapshots, v1)."""
    with np.load(fileobj, allow_pickle=False) as z:
        meta = json.loads(str(z["__meta__"]))
        arrays = {k: z[k] for k in z.files if k != "__meta__"
                  and k != "__manifest__"}
    return _apply_arrays(engine_state, meta, arrays)


# ---------------------------------------------------------------------------
# v2 manifest
# ---------------------------------------------------------------------------


def _crc(arr: np.ndarray) -> int:
    # buffer-protocol view, not .tobytes(): no per-leaf bytes copy on
    # the save path (feature state can be the bulk of host memory)
    return zlib.crc32(memoryview(np.ascontiguousarray(arr)).cast("B"))


def _spec_of_arrays(arrays: dict) -> dict:
    return {k: [list(np.shape(a)), str(np.asarray(a).dtype)]
            for k, a in sorted(arrays.items())}


def _fingerprint(spec: dict) -> str:
    """Structural fingerprint: sha256 over every leaf's key/shape/dtype.
    This IS the materialized config/feature-spec contract — a window
    count, capacity, model width, or dtype change all change it."""
    blob = json.dumps(spec, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(blob.encode()).hexdigest()


def _template_spec(engine_state) -> dict:
    """Leaf spec of a restore template WITHOUT materializing device
    arrays to host (shape/dtype attributes only)."""
    out = {}
    for prefix, tree in (("fs", engine_state.feature_state),
                         ("p", engine_state.params),
                         ("s", engine_state.scaler)):
        for i, (leaf, shape) in enumerate(_leaves_on_disk(tree)):
            dt = getattr(leaf, "dtype", None)
            if dt is None:
                dt = np.asarray(leaf).dtype
            out[f"{prefix}_{i}"] = [list(shape), str(dt)]
    return dict(sorted(out.items()))


def _parse_entry(data: bytes):
    """npz bytes → (meta, manifest|None, manifest_raw|None, arrays).

    Raises :class:`CorruptCheckpointError` with reason ``truncated`` for
    unreadable/partial bytes and ``checksum`` when the zip layer's own
    entry CRC catches a bit-flip."""
    try:
        with np.load(_io.BytesIO(data), allow_pickle=False) as z:
            files = set(z.files)
            meta = json.loads(str(z["__meta__"]))
            man_raw = (str(z["__manifest__"])
                       if "__manifest__" in files else None)
            arrays = {k: z[k] for k in files
                      if k not in ("__meta__", "__manifest__")}
    except zipfile.BadZipFile as e:
        reason = "checksum" if "CRC-32" in str(e) else "truncated"
        raise CorruptCheckpointError(reason, str(e)) from None
    except (KeyError, EOFError, OSError, ValueError) as e:
        raise CorruptCheckpointError(
            "truncated", f"{type(e).__name__}: {e}") from None
    manifest = None
    if man_raw is not None:
        try:
            manifest = json.loads(man_raw)
        except ValueError as e:
            raise CorruptCheckpointError(
                "truncated", f"manifest unparseable: {e}") from None
    return meta, manifest, man_raw, arrays


def _write_checkpoint_npz(fileobj, arrays: dict, meta: dict,
                          manifest: dict) -> None:
    """Stream the checkpoint npz into ``fileobj`` (np.savez writes one
    zip entry per array — peak memory stays one leaf, not the whole
    checkpoint)."""
    np.savez(fileobj,
             __meta__=json.dumps(meta),
             __manifest__=json.dumps(manifest, sort_keys=True,
                                     separators=(",", ":")),
             **arrays)


# ---------------------------------------------------------------------------
# Storage backends
# ---------------------------------------------------------------------------


class _LocalBackend:
    """Flat-directory file storage for the checkpoint lineage. Names are
    bare filenames; the lineage API exposes full paths."""

    kind = "local"

    def __init__(self, directory: str):
        self.directory = directory
        os.makedirs(directory, exist_ok=True)

    def path_of(self, name: str) -> str:
        return os.path.join(self.directory, name)

    def name_of(self, path: str) -> str:
        return os.path.basename(path)

    def read(self, name: str) -> bytes:
        try:
            with open(self.path_of(name), "rb") as f:
                return f.read()
        except FileNotFoundError:
            raise KeyError(name) from None

    def write(self, name: str, data: bytes) -> None:
        self.write_via(name, lambda f: f.write(data))

    def write_via(self, name: str, writer) -> int:
        """tmp-write + atomic rename around a streaming ``writer(f)``
        callback; returns the committed byte size."""
        path = self.path_of(name)
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            writer(f)
        os.replace(tmp, path)  # atomic on POSIX
        return os.path.getsize(path)

    def delete(self, name: str) -> None:
        try:
            os.remove(self.path_of(name))
        except FileNotFoundError:
            pass

    def move(self, name: str, new_name: str) -> None:
        os.replace(self.path_of(name), self.path_of(new_name))

    def exists(self, name: str) -> bool:
        return os.path.exists(self.path_of(name))

    def list_names(self) -> List[str]:
        return sorted(os.listdir(self.directory))

    def info(self, name: str) -> dict:
        try:
            st = os.stat(self.path_of(name))
            return {"size": st.st_size, "mtime": st.st_mtime}
        except OSError:
            return {"size": None, "mtime": None}

    def sweep_orphan_tmps(self) -> List[str]:
        """Crash hygiene: a crash between the tmp write and os.replace
        leaks ``ckpt-*.npz.tmp`` forever — remove them at construction
        (they are by definition not part of the committed lineage)."""
        swept = []
        for f in self.list_names():
            if f.startswith("ckpt-") and f.endswith(".tmp"):
                self.delete(f)
                swept.append(f)
        return swept


class _StoreBackend:
    """Object-store storage with flaky-store hardening: every op runs
    through ``with_retries`` (original-typed error propagation — a
    KeyError for a missing key is NOT retried) and an optional per-op
    timeout that surfaces a hung call as a transient within the budget
    instead of wedging the caller. Object PUTs are atomic, so no
    tmp+rename dance is needed."""

    kind = "store"

    def __init__(self, store, prefix: str, op_timeout_s: float = 0.0,
                 op_attempts: int = 3):
        self.store = store
        self.prefix = prefix.strip("/")
        self.op_timeout_s = float(op_timeout_s)
        self.op_attempts = max(1, int(op_attempts))

    def _retrying(self, fn):
        from real_time_fraud_detection_system_tpu.runtime.faults import (
            RetryPolicy,
            TransientError,
            with_retries,
        )

        def attempt():
            if self.op_timeout_s <= 0:
                return fn()
            box: dict = {}

            def run():
                try:
                    box["v"] = fn()
                # rtfdslint: disable=broad-exception-catch (thread-boundary transport: the op-timeout thread parks the ORIGINAL exception for the caller to re-raise through the typed retry policy)
                except BaseException as e:  # reported to the caller thread
                    box["e"] = e

            t = threading.Thread(target=run, daemon=True,
                                 name="ckpt-store-op")
            t.start()
            t.join(self.op_timeout_s)
            if t.is_alive():
                # the op keeps running in its abandoned thread — the
                # retry opens a fresh attempt rather than waiting forever
                raise TransientError(
                    f"store op timed out after {self.op_timeout_s:.1f}s")
            if "e" in box:
                raise box["e"]
            return box.get("v")

        return with_retries(
            attempt,
            RetryPolicy(max_attempts=self.op_attempts, base_delay_s=0.1,
                        multiplier=2.0, max_delay_s=2.0),
            retry_on=(TransientError, ConnectionError, TimeoutError,
                      OSError),
        )

    def path_of(self, name: str) -> str:
        return f"{self.prefix}/{name}" if self.prefix else name

    def name_of(self, path: str) -> str:
        pre = self.prefix + "/" if self.prefix else ""
        return path[len(pre):] if path.startswith(pre) else path

    def read(self, name: str) -> bytes:
        return self._retrying(lambda: self.store.get(self.path_of(name)))

    def write(self, name: str, data: bytes) -> None:
        self._retrying(lambda: self.store.put(self.path_of(name), data))

    def write_via(self, name: str, writer) -> int:
        # an object PUT needs the whole body up front, so the store
        # plane buffers; only the local plane gets true streaming
        buf = _io.BytesIO()
        writer(buf)
        data = buf.getvalue()
        self.write(name, data)
        return len(data)

    def delete(self, name: str) -> None:
        self._retrying(lambda: self.store.delete(self.path_of(name)))

    def move(self, name: str, new_name: str) -> None:
        src, dst = self.path_of(name), self.path_of(new_name)
        move = getattr(self.store, "move", None)
        if move is not None:
            self._retrying(lambda: move(src, dst))
        else:  # duck-typed store without move: copy-then-delete
            data = self._retrying(lambda: self.store.get(src))
            self._retrying(lambda: self.store.put(dst, data))
            self._retrying(lambda: self.store.delete(src))

    def exists(self, name: str) -> bool:
        return self._retrying(
            lambda: self.store.exists(self.path_of(name)))

    def list_names(self) -> List[str]:
        pre = self.prefix + "/" if self.prefix else ""
        keys = self._retrying(lambda: self.store.list(pre))
        # Flat-directory semantics (matching _LocalBackend's listdir):
        # keys nested deeper under the prefix belong to OTHER lineages
        # (e.g. a sibling job's prefix) and must not be GC'd/restored.
        return sorted(k[len(pre):] for k in keys
                      if "/" not in k[len(pre):])

    def info(self, name: str) -> dict:
        head = getattr(self.store, "head", None)
        if head is None:
            return {"size": None, "mtime": None}
        try:
            h = self._retrying(lambda: head(self.path_of(name)))
        except KeyError:
            return {"size": None, "mtime": None}
        mtime = None
        etag = str(h.get("etag", ""))
        if etag.isdigit():  # LocalStore etag = mtime_ns
            mtime = int(etag) / 1e9
        return {"size": h.get("size"), "mtime": mtime}


# ---------------------------------------------------------------------------
# Checkpointers
# ---------------------------------------------------------------------------


class _CheckpointerBase:
    """Shared lineage logic over a storage backend: v2 manifests, delta
    chains, verified restore with quarantine + fallback, chain-aware
    retention GC. Subclasses bind the backend and keep their historical
    constructor signatures."""

    def __init__(self, backend, keep: int = 3, full_every: int = 1):
        self._backend = backend
        self.keep = keep
        self.full_every = max(1, int(full_every))
        self.incarnation = uuid.uuid4().hex[:12]
        # (name, manifest_raw, manifest) of the last save THIS writer
        # made — the delta base. A fresh process always starts full.
        self._last: Optional[Tuple[str, str, dict]] = None
        self._since_full = 0
        self._manifest_cache: dict = {}

    # -- lineage API ------------------------------------------------------

    def _live_names(self) -> List[str]:
        return [
            f for f in self._backend.list_names()
            if f.startswith("ckpt-") and f.endswith(".npz")
            and ".tmp" not in f
        ]

    def list_checkpoints(self) -> list:
        """Live checkpoint paths, oldest → newest (lineage API used by
        the crash-recovery fence, ``runtime/faults._FencedCheckpointer``)."""
        return [self._backend.path_of(n) for n in self._live_names()]

    def latest(self) -> Optional[str]:
        ckpts = self.list_checkpoints()
        return ckpts[-1] if ckpts else None

    def exists(self, path: str) -> bool:
        return self._backend.exists(self._backend.name_of(path))

    def quarantine(self, paths, token: str,
                   clear_previous: bool = True) -> None:
        """Hide checkpoints from ``latest()``/GC: rename to
        ``stale-<token>-…`` (bytes preserved — forensics, not deletion).
        The fresh-start fence clears any earlier stash first so repeated
        fresh runs keep one quarantine, not a pile; the corruption path
        passes ``clear_previous=False`` so a fallback cascade never
        destroys the evidence it just stashed."""
        if clear_previous:
            for old in self._backend.list_names():
                if old.startswith("stale-") and old.endswith(".npz"):
                    self._backend.delete(old)
        for p in paths:
            name = self._backend.name_of(p)
            if self._backend.exists(name):
                self._backend.move(name, f"stale-{token}-{name}")
            self._manifest_cache.pop(name, None)
            if self._last is not None and self._last[0] == name:
                # the writer's delta base just left the lineage — the
                # next save must be a full, never a delta chained to a
                # quarantined entry
                self._last = None

    # -- save -------------------------------------------------------------

    def save(self, engine_state) -> str:
        t0 = time.perf_counter()
        arrays, meta = _state_arrays(engine_state)
        crcs = {k: _crc(a) for k, a in arrays.items()}
        spec = _spec_of_arrays(arrays)
        fp = _fingerprint(spec)
        step = meta["batches_done"]
        kind = "full"
        name = f"ckpt-{step:010d}.npz"
        stored = arrays
        base = base_crc = None
        if (self.full_every > 1 and self._last is not None
                and self._since_full + 1 < self.full_every):
            last_name, last_raw, last_man = self._last
            dname = f"ckpt-{step:010d}-delta.npz"
            if (last_man.get("fingerprint") == fp
                    and dname != last_name
                    and not self._backend.exists(dname)
                    # the base may have been quarantined/GC'd since the
                    # writer last saw it (fallback restore in the same
                    # process); chaining to a gone base would make every
                    # later delta unrestorable until the next full
                    and self._backend.exists(last_name)):
                kind = "delta"
                name = dname
                base = last_name
                base_crc = zlib.crc32(last_raw.encode())
                last_crcs = last_man.get("crcs", {})
                stored = {k: a for k, a in arrays.items()
                          if crcs[k] != last_crcs.get(k)}
        manifest = {
            "format": 2,
            "kind": kind,
            "incarnation": self.incarnation,
            "batches_done": step,
            "fingerprint": fp,
            "spec": spec,
            "crcs": crcs,
            "stored": sorted(stored),
            "base": base,
            "base_manifest_crc": base_crc,
        }
        nbytes = self._backend.write_via(
            name, lambda f: _write_checkpoint_npz(f, stored, meta,
                                                  manifest))
        man_raw = json.dumps(manifest, sort_keys=True,
                             separators=(",", ":"))
        self._last = (name, man_raw, manifest)
        self._since_full = 0 if kind == "full" else self._since_full + 1
        self._manifest_cache[name] = manifest
        self._gc()
        reg = get_registry()
        reg.gauge("rtfds_last_checkpoint_unix_seconds",
                  "wall-clock time of the last checkpoint save").set(
            time.time())
        reg.gauge("rtfds_checkpoint_lineage_depth",
                  "live checkpoints in the lineage").set(
            len(self._live_names()))
        # a fresh save supersedes any fallback restore: the durable
        # plane is healthy again (healthz drops "degraded")
        reg.gauge("rtfds_checkpoint_serving_fallback",
                  "1 while the engine serves off a fallback (non-newest) "
                  "checkpoint restore").set(0)
        _observe_checkpoint("save", self._backend.kind, t0, nbytes,
                            step, kind=kind)
        return self._backend.path_of(name)

    # -- restore ----------------------------------------------------------

    def _manifest_of(self, name: str) -> Optional[dict]:
        man = self._manifest_cache.get(name)
        if man is not None:
            return man
        try:
            _, man, _, _ = _parse_entry(self._backend.read(name))
        except (KeyError, CorruptCheckpointError):
            return None
        if man is not None:
            self._manifest_cache[name] = man
        return man

    def _resolve_chain(self, name: str, template=None) -> Tuple[dict, dict]:
        """Load + verify the checkpoint at ``name`` (following its delta
        chain) → (meta, composed arrays). Raises
        :class:`CorruptCheckpointError` on any broken invariant."""
        entries = []  # tip-first: (name, meta, manifest, arrays)
        seen = set()
        cur: Optional[str] = name
        expect_crc: Optional[int] = None
        while cur is not None:
            if cur in seen:
                raise CorruptCheckpointError(
                    "checksum", f"delta chain cycle at {cur}")
            seen.add(cur)
            try:
                data = self._backend.read(cur)
            except KeyError:
                raise CorruptCheckpointError(
                    "truncated", f"chain entry {cur} is missing") from None
            meta, man, man_raw, arrays = _parse_entry(data)
            if expect_crc is not None:
                if man_raw is None or zlib.crc32(
                        man_raw.encode()) != expect_crc:
                    raise CorruptCheckpointError(
                        "checksum",
                        f"chain link mismatch: {cur} is not the base its "
                        f"delta was built from")
            entries.append((cur, meta, man, arrays))
            if man is not None and man.get("kind") == "delta":
                base = man.get("base")
                if not base:
                    raise CorruptCheckpointError(
                        "truncated", f"delta {cur} names no base")
                expect_crc = man.get("base_manifest_crc")
                cur = base
            else:
                cur = None
        tip_name, tip_meta, tip_man, _ = entries[0]
        # compose oldest → newest: the full provides every leaf, deltas
        # overlay the leaves they stored
        composed: dict = {}
        for _, _, _, arrays in reversed(entries):
            composed.update(arrays)
        if tip_man is not None:
            crcs = tip_man.get("crcs", {})
            missing = [k for k in crcs if k not in composed]
            if missing:
                raise CorruptCheckpointError(
                    "truncated",
                    f"composed state is missing leaves {missing[:4]}")
            for k, want in crcs.items():
                if _crc(composed[k]) != int(want):
                    raise CorruptCheckpointError(
                        "checksum", f"leaf {k} fails its manifest CRC32")
        if template is not None:
            self._check_template(tip_name, tip_meta, tip_man, composed,
                                 template)
        return tip_meta, composed

    @staticmethod
    def _check_topology(name, meta, template) -> None:
        """Refuse a healthy checkpoint written under a different process
        topology (vs quarantine-and-fallback, which is for corruption).

        Allowed: identical topology (count + this process's id), and a
        single-process GLOBAL checkpoint restored by a multi-process
        fleet — the engine's elastic adoption re-slices it per process
        (``parallel.mesh.adopt_process_slice``, the same reshard
        machinery as width changes). Everything else names its fix."""
        ck_pc = int(meta.get("process_count", 1) or 1)
        ck_pid = int(meta.get("process_id", 0) or 0)
        tpl_pc = int(getattr(template, "process_count", 1) or 1)
        tpl_pid = int(getattr(template, "process_id", 0) or 0)
        if ck_pc == tpl_pc and (ck_pc == 1 or ck_pid == tpl_pid):
            if ck_pc > 1:
                # Same fleet, same process — but a per-process WIDTH
                # change moves residue blocks (ownership is
                # key % (P·L)): keys migrate BETWEEN processes, which
                # no per-process reshard can do. Refuse, naming the
                # merge path, instead of silently splitting histories.
                ck_ld = int(meta.get("layout_devices", 1) or 1)
                tpl_ld = int(getattr(template, "layout_devices", 1)
                             or 1)
                if ck_ld != tpl_ld:
                    raise CheckpointTopologyError(
                        f"{name} was written at {ck_ld} device(s) per "
                        f"process but this engine serves {tpl_ld} — in "
                        f"a {ck_pc}-process fleet that changes the "
                        "residue-block ownership (key % (P·L)), moving "
                        "keys BETWEEN processes: merge the fleet's "
                        "checkpoints to a global state (parallel.mesh."
                        "merge_process_states → save single-process) "
                        "and let the new fleet's elastic 1→N adoption "
                        "re-slice it, or relaunch at the original "
                        f"--devices {ck_ld}")
            return
        if ck_pc == 1 and tpl_pc > 1:
            return  # sanctioned 1→P adoption (engine re-slices)
        if ck_pc == tpl_pc:
            raise CheckpointTopologyError(
                f"{name} was written by process {ck_pid} of the "
                f"{ck_pc}-process fleet, but this engine is process "
                f"{tpl_pid} — each process restores its OWN residue "
                "block; point every worker at its own proc-NN "
                "checkpoint directory (the launcher does this when the "
                "checkpoint root and process ids are unchanged)")
        raise CheckpointTopologyError(
            f"{name} was written by a {ck_pc}-process fleet; this "
            f"engine serves a {tpl_pc}-process topology. A per-process "
            "checkpoint holds only its residue block's keys, so a "
            "process-count change cannot restore directly: merge every "
            "process's final checkpoint into one global state "
            "(parallel.mesh.merge_process_states), save it from a "
            "single-process engine, and let the new fleet's elastic "
            "1→N adoption re-slice it — or relaunch at the original "
            f"--num-processes {ck_pc}")

    @staticmethod
    def _check_template(name, meta, manifest, arrays, template) -> None:
        """Structural compatibility vs the restore template: leaf counts
        and shapes always; dtypes + the config/feature-spec fingerprint
        for v2 entries (v1 keeps its historical trusting shape check).

        One sanctioned shape exception: when the checkpoint's recorded
        ``layout_devices`` differs from the template engine's, the
        FEATURE-STATE leaves may legitimately carry different shapes
        (the exact store's per-shard directories are width-dependent —
        stacked ``[n, ...]`` leaves). Those leaves skip the shape
        equality (dtypes and per-leaf CRCs still hold, so corruption is
        still caught) and the engine's ``_ensure_layout`` re-homes them
        via the elastic reshard — which itself hard-fails on a genuine
        capacity mismatch, loudly, instead of this path quarantining a
        healthy cross-width checkpoint."""
        ck_bits = int(meta.get("key_bits") or 32)
        bits = state_key_bits(getattr(template, "feature_state", None))
        if ck_bits != bits:
            raise CheckpointKeyWidthError(
                f"{name} was written at key_bits={ck_bits}; this engine "
                f"states key_bits={bits}. A folded key and a whole one do "
                "not name the same state: restore at the width the "
                "checkpoint was written at, or start the wider "
                "deployment from a fresh state")
        spec = _template_spec(template)
        n_fs = sum(1 for k in spec if k.startswith("fs_"))
        n_p = sum(1 for k in spec if k.startswith("p_"))
        n_s = sum(1 for k in spec if k.startswith("s_"))
        if (meta.get("n_fs"), meta.get("n_p"), meta.get("n_s")) != (
                n_fs, n_p, n_s):
            raise CorruptCheckpointError(
                "incompatible",
                f"{name}: leaf counts {meta.get('n_fs')}/{meta.get('n_p')}"
                f"/{meta.get('n_s')} vs template {n_fs}/{n_p}/{n_s}")
        cross_width = (
            meta.get("layout_devices") is not None
            and int(meta["layout_devices"]) != int(
                getattr(template, "layout_devices", 1) or 1))
        fs_names = meta.get("fs_leaves") or {}

        def width_dependent(k: str) -> bool:
            # Only the per-shard planes legitimately change shape with
            # width: key directories (stacked [n, ...] leaves) and
            # sketch replicas. Window tables are global [cap, NB] on
            # disk at EVERY width, so a capacity mismatch there must stay an
            # 'incompatible' quarantine-and-fallback, not leak through
            # to a hard reshard crash. Writers without leaf names
            # (pre-sharded-exact) never produced width-dependent
            # shapes, so they keep the strict check.
            path = fs_names.get(k, "")
            return "_dir" in path or "cms" in path

        for k, (shape, dtype) in spec.items():
            a = arrays.get(k)
            if a is None:
                raise CorruptCheckpointError(
                    "truncated", f"{name}: leaf {k} absent")
            if list(np.shape(a)) != list(shape) and not (
                    cross_width and k.startswith("fs_")
                    and width_dependent(k)):
                raise CorruptCheckpointError(
                    "incompatible",
                    f"{name}: leaf {k} shape {list(np.shape(a))} vs "
                    f"template {list(shape)}")
            if manifest is not None and str(a.dtype) != str(dtype):
                raise CorruptCheckpointError(
                    "incompatible",
                    f"{name}: leaf {k} dtype {a.dtype} vs template "
                    f"{dtype}")

    def _note_corrupt(self, name: str, err: CorruptCheckpointError) -> None:
        reg = get_registry()
        reg.counter(
            "rtfds_checkpoint_corrupt_total",
            "checkpoints that failed restore verification, by reason",
            reason=err.reason).inc()
        rec = active_recorder()
        if rec is not None:
            rec.record_event("checkpoint_fallback", path=name,
                             reason=err.reason, detail=err.detail[:200])
        from real_time_fraud_detection_system_tpu.utils.logging import (
            get_logger,
        )

        get_logger("checkpoint").error(
            "corrupt checkpoint %s (%s: %s) — quarantining and falling "
            "back down the lineage", name, err.reason, err.detail[:200])
        self.quarantine([self._backend.path_of(name)],
                        uuid.uuid4().hex[:8], clear_previous=False)

    def restore(self, engine_state, path: Optional[str] = None):
        """Restore into an EngineState template (same model/config
        shapes). Verifies the manifest (checksums + structural
        compatibility + delta chain) and, on any mismatch, quarantines
        the corrupt entry and falls back to the next-newest valid one.

        Returns the mutated engine_state, or None when no (valid)
        checkpoint exists.
        """
        names = self._live_names()
        if path is not None:
            want = self._backend.name_of(path)
            names = [n for n in names if n <= want]
            if want not in names and self._backend.exists(want):
                names.append(want)
        if not names:
            return None
        tip = names[-1]
        corrupt = 0
        for n in reversed(names):
            t0 = time.perf_counter()
            try:
                meta, arrays = self._resolve_chain(n, template=engine_state)
            except CorruptCheckpointError as e:
                corrupt += 1
                self._note_corrupt(n, e)
                continue
            # AFTER the corruption verdict, BEFORE the template is
            # mutated: a topology mismatch is a refusal (raises), never
            # a quarantine — the checkpoint is healthy and the whole
            # lineage shares its topology, so falling back would only
            # rewind the fleet
            self._check_topology(n, meta, engine_state)
            out = _apply_arrays(engine_state, meta, arrays)
            nbytes = sum(a.nbytes for a in arrays.values())
            _observe_checkpoint("restore", self._backend.kind, t0, nbytes,
                                int(out.batches_done))
            if corrupt:
                reg = get_registry()
                reg.counter(
                    "rtfds_checkpoint_fallbacks_total",
                    "restores that fell back past corrupt checkpoints"
                ).inc()
                reg.gauge(
                    "rtfds_checkpoint_serving_fallback",
                    "1 while the engine serves off a fallback "
                    "(non-newest) checkpoint restore").set(1)
                rec = active_recorder()
                if rec is not None:
                    rec.record_event(
                        "checkpoint_fallback", restored=n, skipped=corrupt,
                        from_tip=tip,
                        batches_done=int(out.batches_done))
            return out
        return None  # every lineage entry failed verification

    # -- verification (CLI preflight) -------------------------------------

    def verify_all(self, deep: bool = True) -> List[dict]:
        """Report on every live checkpoint WITHOUT quarantining or
        counting metrics. ``deep=True`` re-checksums each entry AND its
        composed delta chain (the deploy preflight behind ``rtfds ckpt
        --verify``: O(chain) reads per tip). ``deep=False`` is the cheap
        listing verdict: one read per entry — the zip layer's own entry
        CRCs still catch bit-flips in the entry itself, but a broken
        chain link only surfaces under ``deep``."""
        now = time.time()  # vs backend mtime: cross-process wall age
        out = []
        for n in self._live_names():
            info = self._backend.info(n)
            entry = {
                "path": self._backend.path_of(n),
                "size": info.get("size"),
                # rtfdslint: disable=wall-clock-duration (age vs the backend's mtime — a wall-clock stamp written by ANOTHER process; perf_counter has no cross-process meaning)
                "age_s": (round(now - info["mtime"], 1)
                          if info.get("mtime") else None),
            }
            try:
                meta, man, _, _ = _parse_entry(self._backend.read(n))
                entry["kind"] = (man.get("kind", "full") if man else "v1")
                entry["batches_done"] = meta.get("batches_done")
                entry["incarnation"] = (man or {}).get("incarnation")
                if deep:
                    self._resolve_chain(n)
                entry["valid"] = True
            except CorruptCheckpointError as e:
                entry["valid"] = False
                entry["reason"] = e.reason
                entry["detail"] = e.detail[:200]
            except KeyError:
                entry["valid"] = False
                entry["reason"] = "truncated"
                entry["detail"] = "entry vanished mid-verify"
            out.append(entry)
        return out

    def manifest(self, path: str) -> dict:
        """Meta + manifest of one checkpoint (``rtfds ckpt --inspect``).
        v1 entries return their meta under ``{"format": 1}``."""
        name = self._backend.name_of(path)
        meta, man, _, _ = _parse_entry(self._backend.read(name))
        if man is None:
            return {"format": 1, "meta": meta}
        return {**man, "meta": meta}

    # -- retention --------------------------------------------------------

    def _gc(self) -> None:
        names = self._live_names()
        if len(names) <= self.keep:
            return
        keep_set = set(names[-self.keep:])
        # chain-aware: never GC a base some kept delta still composes
        # from — deleting it would break every restore of that delta
        frontier = list(keep_set)
        live = set(names)
        while frontier:
            n = frontier.pop()
            man = self._manifest_of(n)
            base = (man or {}).get("base") if (man or {}).get(
                "kind") == "delta" else None
            if base and base in live and base not in keep_set:
                keep_set.add(base)
                frontier.append(base)
        for n in names:
            if n not in keep_set:
                self._backend.delete(n)
                self._manifest_cache.pop(n, None)


class Checkpointer(_CheckpointerBase):
    """Filesystem checkpointer (tmp write + atomic rename). Construction
    sweeps ``ckpt-*.npz.tmp`` orphans a crash between the tmp write and
    ``os.replace`` would otherwise leak forever."""

    def __init__(self, directory: str, keep: int = 3, full_every: int = 1):
        self.directory = directory
        super().__init__(_LocalBackend(directory), keep=keep,
                         full_every=full_every)
        self._backend.sweep_orphan_tmps()


class StoreCheckpointer(_CheckpointerBase):
    """Checkpointer over an object store — the reference's
    ``checkpointLocation`` on s3a (``fraud_detection.py:63``,
    ``kafka_s3_sink_*.py:11``): streaming state durable in MinIO/S3, not
    on an ephemeral host disk. Object PUTs are atomic. Same
    save/restore/latest contract as :class:`Checkpointer`; ``store`` is
    any :mod:`..io.store` object. Store ops are hardened: retried with
    original-typed error propagation, optional per-op timeout
    (``op_timeout_s``; 0 = wait)."""

    def __init__(self, store, prefix: str = "checkpoints", keep: int = 3,
                 full_every: int = 1, op_timeout_s: float = 0.0,
                 op_attempts: int = 3):
        self.store = store
        self.prefix = prefix.strip("/")
        super().__init__(
            _StoreBackend(store, prefix, op_timeout_s=op_timeout_s,
                          op_attempts=op_attempts),
            keep=keep, full_every=full_every)

    def _list(self):
        """Historical internal API (tests + retention introspection):
        live checkpoint KEYS under the prefix."""
        return [self._backend.path_of(n) for n in self._live_names()]


def make_checkpointer(path_or_url: str, keep: int = 3, full_every: int = 1,
                      op_timeout_s: float = 0.0, op_attempts: int = 3):
    """``s3://bucket/prefix`` → :class:`StoreCheckpointer`; local path →
    :class:`Checkpointer`."""
    if path_or_url.startswith("s3://"):
        from real_time_fraud_detection_system_tpu.io.store import make_store

        return StoreCheckpointer(make_store(path_or_url), prefix="",
                                 keep=keep, full_every=full_every,
                                 op_timeout_s=op_timeout_s,
                                 op_attempts=op_attempts)
    return Checkpointer(path_or_url, keep=keep, full_every=full_every)


def feature_state_report(man: dict) -> Optional[dict]:
    """Operator view of a checkpoint's feature-state plane from the
    MANIFEST alone (no array loads): named leaves with per-shard byte
    attribution, plus the per-shard directory occupancy the writer
    recorded — so state skew across shards is visible from ``rtfds ckpt
    --inspect`` without restoring the checkpoint.

    Returns None when the entry predates leaf naming (v1, or a pre-
    sharded-state v2 manifest)."""
    meta = man.get("meta") or {}
    names = meta.get("fs_leaves") or {}
    spec = man.get("spec") or {}
    if not names or not spec:
        return None
    layout = int(meta.get("layout_devices", 1) or 1)
    stored = set(man.get("stored") or [])
    leaves = []
    total = 0
    for k in sorted(names, key=lambda k: int(k.split("_")[1])):
        if k not in spec:
            continue
        shape, dtype = spec[k]
        nbytes = int(np.prod(shape, dtype=np.int64) if shape else 1) \
            * np.dtype(dtype).itemsize
        total += nbytes
        row = {"leaf": k, "path": names[k], "shape": shape,
               "dtype": dtype, "bytes": nbytes}
        if stored:
            # delta checkpoints: which state leaves actually churned
            row["stored_in_entry"] = k in stored
        if layout > 1 and shape and int(shape[0]) == layout:
            # stacked per-shard leaf (directories, sketch replicas)
            row["per_shard_bytes"] = nbytes // layout
        leaves.append(row)
    out: dict = {"layout_devices": layout, "total_bytes": total,
                 "leaves": leaves}
    pc = int(meta.get("process_count", 1) or 1)
    if pc > 1:
        # fleet writer: this entry holds ONE process's residue block
        out["process_count"] = pc
        out["process_id"] = int(meta.get("process_id", 0) or 0)
        out["fleet_shards_total"] = pc * layout
    occ = meta.get("feature_state_occupancy")
    if occ:
        out["occupancy_per_shard"] = occ
        worst = {
            t: int(max(range(len(v)), key=lambda s: v[s]))
            for t, v in occ.items() if v}
        out["worst_shard"] = {
            t: {"shard": s, "occupied": occ[t][s]}
            for t, s in worst.items()}
    cold = cold_tier_report(meta.get("cold_lineage"))
    if cold is not None:
        out["cold"] = cold
    return out


def cold_tier_report(lineage: Optional[dict]) -> Optional[dict]:
    """Cold-tier plane of ``rtfds ckpt --inspect``, from MANIFESTS alone
    (no segment-blob reads): the lineage the checkpoint recorded, plus a
    per-segment CRC VERDICT against the cold store's on-disk manifests —
    ``ok`` (manifest present, crc matches the lineage), ``mismatch``
    (the segment was rewritten/corrupted since the save), ``missing``
    (segment gone — e.g. gc after a newer checkpoint; its keys degrade
    to CMS on restore), ``unavailable`` (cold store unreachable)."""
    if not lineage:
        return None
    segs = list(lineage.get("segments", []))
    out = {
        "cold_store": lineage.get("cold_store", ""),
        "segments": len(segs),
        "total_keys": int(lineage.get("total_keys", 0) or 0),
        "total_bytes": int(lineage.get("total_bytes", 0) or 0),
    }
    rows = []
    for s in segs:
        seq = int(s["seq"])
        row = {"seq": seq, "blob": s.get("blob"),
               "bytes": int(s.get("bytes", 0) or 0),
               "keys": s.get("keys", {})}
        row["crc_verdict"] = _cold_seg_verdict(
            lineage.get("cold_store", ""), seq, s.get("crc"))
        rows.append(row)
    out["segment_rows"] = rows
    verdicts = {r["crc_verdict"] for r in rows}
    out["crc_verdict"] = ("ok" if not verdicts or verdicts == {"ok"}
                          else "mismatch" if "mismatch" in verdicts
                          else "missing" if "missing" in verdicts
                          else "unavailable")
    return out


def _cold_seg_verdict(cold_store: str, seq: int, crc) -> str:
    """Best-effort on-disk manifest check for one lineage segment."""
    if not cold_store:
        return "unavailable"
    name = f"seg-{seq:08d}.json"
    try:
        if cold_store.startswith("s3://"):
            from real_time_fraud_detection_system_tpu.io.store import (
                make_store,
            )

            data = _StoreBackend(make_store(cold_store),
                                 prefix="").read(name)
        else:
            data = _LocalBackend(cold_store).read(name)
        man = json.loads(data.decode("utf-8"))
    except KeyError:
        return "missing"
    # rtfdslint: disable=broad-exception-catch (inspect is read-only forensics: ANY failure to reach/parse the cold store must degrade to a verdict, never kill the inspect)
    except Exception:
        return "unavailable"
    return "ok" if crc is not None and int(man.get("crc", -1)) == \
        int(crc) else "mismatch"
