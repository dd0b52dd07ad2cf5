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

    customer_key: jnp.ndarray  # uint32 [B] — hashed/truncated customer id
    terminal_key: jnp.ndarray  # uint32 [B]
    day: jnp.ndarray  # int32 [B] — days since unix epoch
    tod_s: jnp.ndarray  # int32 [B] — second within day
    amount: jnp.ndarray  # float32 [B] — dollars (display/features)
    label: jnp.ndarray  # int32 [B] — -1 unknown, else 0/1 fraud
    valid: jnp.ndarray  # bool [B]

    @property
    def size(self) -> int:
        return int(self.customer_key.shape[0])


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


def make_batch(
    customer_id: np.ndarray,
    terminal_id: np.ndarray,
    tx_datetime_us: np.ndarray,
    amount_cents: np.ndarray,
    label: Optional[np.ndarray] = None,
    pad_to: Optional[int] = None,
) -> TxBatch:
    """Build a (host-side numpy) TxBatch from columnar int64 inputs."""
    n = len(customer_id)
    m = pad_to if pad_to is not None else n
    if m < n:
        raise ValueError(f"pad_to={m} < batch rows {n}")

    def _pad(a: np.ndarray) -> np.ndarray:
        out = np.zeros(m, dtype=a.dtype)
        out[:n] = a
        return out

    day = (tx_datetime_us // US_PER_DAY).astype(np.int32)
    tod = ((tx_datetime_us % US_PER_DAY) // 1_000_000).astype(np.int32)
    lab = (label if label is not None else np.full(n, -1)).astype(np.int32)
    valid = np.zeros(m, dtype=bool)
    valid[:n] = True
    return TxBatch(
        customer_key=_pad(fold_key(customer_id)),
        terminal_key=_pad(fold_key(terminal_id)),
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

    def _pad(a):
        a = np.asarray(a)
        out = np.zeros((pad_to,) + a.shape[1:], dtype=a.dtype)
        out[:n] = a
        return out

    return TxBatch(*[_pad(x) for x in batch])


def pack_batch(batch: TxBatch) -> np.ndarray:
    """Host-side TxBatch → ONE int32 array [7, B] for a single H2D copy.

    Each device transfer pays a fixed per-call overhead, so moving
    a batch as 7 separate leaves costs 7× the fixed overhead of moving it
    as one array. uint32 keys and float32 amounts travel as their int32
    bit patterns; :func:`unpack_batch` bitcasts them back inside jit, so
    the round trip is exact.
    """
    return np.stack([
        np.asarray(batch.customer_key).view(np.int32),
        np.asarray(batch.terminal_key).view(np.int32),
        np.asarray(batch.day),
        np.asarray(batch.tod_s),
        np.asarray(batch.amount).view(np.int32),
        np.asarray(batch.label),
        np.asarray(batch.valid).astype(np.int32),
    ])


def unpack_batch(packed: jnp.ndarray) -> TxBatch:
    """Device-side inverse of :func:`pack_batch` (inside jit; free after
    XLA fusion — bitcasts and a compare, no copies of consequence)."""
    import jax

    bitcast = jax.lax.bitcast_convert_type
    return TxBatch(
        customer_key=bitcast(packed[0], jnp.uint32),
        terminal_key=bitcast(packed[1], jnp.uint32),
        day=packed[2],
        tod_s=packed[3],
        amount=bitcast(packed[4], jnp.float32),
        label=packed[5],
        valid=packed[6] != 0,
    )
