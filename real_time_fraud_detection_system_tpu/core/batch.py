"""Device micro-batch representation.

A ``TxBatch`` is the columnar unit of work the jitted step consumes — the
TPU-side analogue of one Spark micro-batch DataFrame (reference
``foreachBatch``, ``kafka_s3_sink_transactions.py:160``). Ragged stream
batches are padded to a small set of bucket sizes so the jit cache stays warm
(SURVEY §7 "ragged micro-batches").

Device arrays are 32-bit on purpose (TPU-friendly, no jax x64 flag):
timestamps are carried as (day, second-of-day) pairs instead of µs epochs;
64-bit identifiers stay host-side and rows are re-joined by position after
scoring. Weekday/night flags derive in-kernel from (day, tod_s).

A key is as wide as the deployment states (``FeatureConfig.key_bits``).
At 32 an id is xor-folded to one uint32 word (:func:`fold_key`) and a key
column is ``uint32 [B]``. At 64 the id is split into its two words
(:func:`split_key`) and a key column is ``uint32 [2, B]`` — row 0 the low
word, row 1 the high: words first, so each is a plain ``[B]`` vector on
the chip. On the host a key is ``np.uint32`` or ``np.uint64``
(:func:`host_keys`).
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Sequence

import jax.numpy as jnp
import numpy as np

US_PER_DAY = 86_400_000_000


class TxBatch(NamedTuple):
    """Columnar transaction micro-batch (pytree of device arrays).

    All arrays have leading dim B (padded bucket size). ``valid`` masks the
    padding; padded rows never touch state or sinks.
    """

    customer_key: jnp.ndarray  # uint32 [B] — folded id; [2, B] at 64 bits
    terminal_key: jnp.ndarray  # uint32 [B] | [2, B]
    day: jnp.ndarray  # int32 [B] — days since unix epoch
    tod_s: jnp.ndarray  # int32 [B] — second within day
    amount: jnp.ndarray  # float32 [B] — dollars (display/features)
    label: jnp.ndarray  # int32 [B] — -1 unknown, else 0/1 fraud
    valid: jnp.ndarray  # bool [B]

    @property
    def size(self) -> int:
        return int(self.valid.shape[0])


def bucket_size(n: int, buckets: Sequence[int]) -> int:
    """Smallest configured bucket that fits n rows (largest bucket if none)."""
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


def fold_key(ids: np.ndarray) -> np.ndarray:
    """Fold int64 ids to uint32 keys (xor-fold hi/lo words)."""
    v = ids.astype(np.uint64)
    return ((v ^ (v >> np.uint64(32))) & np.uint64(0xFFFFFFFF)).astype(np.uint32)


def split_key(ids: np.ndarray) -> np.ndarray:
    """int64 ids → uint32 [2, n]: the low and the high word of each id's
    bit pattern (a negative id is its two's complement, read as uint64)."""
    v = np.ascontiguousarray(ids, dtype=np.int64).view(np.uint64)
    return np.stack([(v & np.uint64(0xFFFFFFFF)).astype(np.uint32),
                     (v >> np.uint64(32)).astype(np.uint32)])


def join_key(words: np.ndarray) -> np.ndarray:
    """Inverse of :func:`split_key`: uint32 [2, ...] → uint64 [...]."""
    words = np.asarray(words, dtype=np.uint32)
    return (words[0].astype(np.uint64)
            | (words[1].astype(np.uint64) << np.uint64(32)))


def host_keys(ids: np.ndarray, key_bits: int = 32) -> np.ndarray:
    """The host's name of each id's key at the stated width: the uint32
    fold at 32 bits, the uint64 bit pattern at 64 — what the cold store
    indexes and what :func:`device_keys` hands the device."""
    if key_bits == 64:
        return np.ascontiguousarray(ids, dtype=np.int64).view(np.uint64)
    return fold_key(np.asarray(ids))


def device_keys(keys: np.ndarray) -> np.ndarray:
    """:func:`host_keys` output → the device's key column: uint32 ``[n]``
    as it stands, uint64 split words-first into uint32 ``[2, n]``."""
    keys = np.asarray(keys)
    if keys.dtype == np.uint64:
        return split_key(keys.view(np.int64))
    return keys.astype(np.uint32)


def wide_id_rows(customer_id: np.ndarray, terminal_id: np.ndarray) -> int:
    """Rows whose customer or terminal id does not fit 32 bits (negative
    ids included): what a 32-bit deployment folds, and may merge."""
    c = np.asarray(customer_id, np.int64).view(np.uint64)
    t = np.asarray(terminal_id, np.int64).view(np.uint64)
    top = np.uint64(0xFFFFFFFF)
    if not c.size or max(c.max(), t.max()) <= top:
        return 0  # the serial-id deployment: two max passes a batch
    return int(np.count_nonzero((c > top) | (t > top)))


def make_batch(
    customer_id: np.ndarray,
    terminal_id: np.ndarray,
    tx_datetime_us: np.ndarray,
    amount_cents: np.ndarray,
    label: Optional[np.ndarray] = None,
    pad_to: Optional[int] = None,
    key_bits: int = 32,
) -> TxBatch:
    """Build a (host-side numpy) TxBatch from columnar int64 inputs."""
    n = len(customer_id)
    m = pad_to if pad_to is not None else n
    if m < n:
        raise ValueError(f"pad_to={m} < batch rows {n}")

    def _pad(a: np.ndarray) -> np.ndarray:
        out = np.zeros(a.shape[:-1] + (m,), dtype=a.dtype)
        out[..., :n] = a
        return out

    key_of = split_key if key_bits == 64 else fold_key

    day = (tx_datetime_us // US_PER_DAY).astype(np.int32)
    tod = ((tx_datetime_us % US_PER_DAY) // 1_000_000).astype(np.int32)
    lab = (label if label is not None else np.full(n, -1)).astype(np.int32)
    valid = np.zeros(m, dtype=bool)
    valid[:n] = True
    return TxBatch(
        customer_key=_pad(key_of(customer_id)),
        terminal_key=_pad(key_of(terminal_id)),
        day=_pad(day),
        tod_s=_pad(tod),
        amount=_pad((amount_cents.astype(np.float64) / 100.0).astype(np.float32)),
        label=_pad(lab),
        valid=valid,
    )


def pad_batch(batch: TxBatch, pad_to: int) -> TxBatch:
    """Pad an existing (numpy) TxBatch up to ``pad_to`` rows."""
    n = batch.size
    if pad_to == n:
        return batch
    if pad_to < n:
        raise ValueError(f"pad_to={pad_to} < batch rows {n}")

    def _pad(a):  # rows are the last axis: [n], or [2, n] for a wide key
        a = np.asarray(a)
        out = np.zeros(a.shape[:-1] + (pad_to,), dtype=a.dtype)
        out[..., :n] = a
        return out

    return TxBatch(*[_pad(x) for x in batch])


def pack_batch(batch: TxBatch) -> np.ndarray:
    """Host-side TxBatch → ONE int32 array [7, B] for a single H2D copy.

    Each device transfer pays a fixed per-call overhead, so moving
    a batch as 7 separate leaves costs 7× the fixed overhead of moving it
    as one array. uint32 keys and float32 amounts travel as their int32
    bit patterns; :func:`unpack_batch` bitcasts them back inside jit, so
    the round trip is exact. A wide batch (``key_bits=64``) is ``[9, B]``:
    the keys' low words where the folded keys ride, their high words in
    two rows behind ``valid`` — rows 2-6 mean what they always meant.
    """
    ck = np.asarray(batch.customer_key).view(np.int32)
    tk = np.asarray(batch.terminal_key).view(np.int32)
    wide = ck.ndim == 2
    return np.stack([
        ck[0] if wide else ck,
        tk[0] if wide else tk,
        np.asarray(batch.day),
        np.asarray(batch.tod_s),
        np.asarray(batch.amount).view(np.int32),
        np.asarray(batch.label),
        np.asarray(batch.valid).astype(np.int32),
    ] + ([ck[1], tk[1]] if wide else []))


def packed_rows(key_bits: int = 32) -> int:
    """Rows of the packed batch at a key width: 7, or 9 at 64 bits."""
    return 9 if key_bits == 64 else 7


def unpack_batch(packed: jnp.ndarray) -> TxBatch:
    """Device-side inverse of :func:`pack_batch` (inside jit; free after
    XLA fusion — bitcasts and a compare, no copies of consequence)."""
    import jax

    bitcast = jax.lax.bitcast_convert_type
    if packed.shape[0] == 9:  # wide keys: [lo, hi] words first
        c_key = bitcast(jnp.stack([packed[0], packed[7]]), jnp.uint32)
        t_key = bitcast(jnp.stack([packed[1], packed[8]]), jnp.uint32)
    else:
        c_key = bitcast(packed[0], jnp.uint32)
        t_key = bitcast(packed[1], jnp.uint32)
    return TxBatch(
        customer_key=c_key,
        terminal_key=t_key,
        day=packed[2],
        tod_s=packed[3],
        amount=bitcast(packed[4], jnp.float32),
        label=packed[5],
        valid=packed[6] != 0,
    )
