"""Integer hashing ops in pure jnp uint32 arithmetic.

Used for key→slot placement in the HBM-resident feature tables and for the
count-min sketch's row hashes. TPU has no native 64-bit int path worth using
here; a finalizer-style 32-bit mixer (splitmix/murmur-finale family) gives
good avalanche with 6 VPU ops per key.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

_M1 = np.uint32(0x7FEB352D)
_M2 = np.uint32(0x846CA68B)


def hash_u32(x: jnp.ndarray, seed: int = 0) -> jnp.ndarray:
    """Mix uint32 keys (vectorized). Distinct seeds give independent hashes."""
    h = x.astype(jnp.uint32) ^ jnp.uint32(0x9E3779B9 * (seed + 1) & 0xFFFFFFFF)
    h = h ^ (h >> 16)
    h = h * _M1
    h = h ^ (h >> 15)
    h = h * _M2
    h = h ^ (h >> 16)
    return h


def hash_key(key: jnp.ndarray, seed: int = 0) -> jnp.ndarray:
    """:func:`hash_u32` of a key column at either width → uint32 [B]. A
    one-word key (``[B]``) is mixed as it always was. A wide key (``[2,
    B]``: low words, high words — ``core/batch.split_key``) mixes BOTH:
    the high word goes through the mixer first and is xored into the low
    one, so two ids that differ in either word differ before the final
    mix (the mixer is a bijection of 32 bits) and collide only as two
    random 32-bit values do — ids whose words xor alike share nothing."""
    if key.ndim == 1:
        return hash_u32(key, seed)
    return hash_u32(key[0] ^ hash_u32(key[1], seed + 0x51), seed)


def slot_of(key: jnp.ndarray, capacity: int, seed: int = 0) -> jnp.ndarray:
    """Key → table slot in [0, capacity). capacity must be a power of two."""
    assert capacity & (capacity - 1) == 0, "capacity must be a power of 2"
    return (hash_u32(key, seed) & jnp.uint32(capacity - 1)).astype(jnp.int32)


def key_slot(key, capacity: int, key_mode: str = "direct",
             n_shards: int = 1):
    """Where a key's row lives: its slot within its owner's block of a
    keyed table (window tables, history state), int32 [B].

    THE layout rule, one chip or a mesh: shard ``key % n_shards`` owns the
    key and keeps it at slot ``(key // n_shards) & (capacity/n_shards - 1)``
    of its contiguous ``capacity / n_shards`` rows (:func:`key_row` is the
    global row). On one shard that is 'direct' — exact for dense serial
    ids below ``capacity``; 'hash' mixes sparse key universes first and
    exists on one shard only: a mesh's layout is owner-modulo and has
    never hashed. 'exact' never comes through here — it routes through
    the key directory (``ops/keydir.admit_slots``). Plain operators, so
    NumPy callers (the state's birth, late labels, reshards) and the
    jitted step share the one rule; the mask is a modulo only for a
    power-of-two local capacity, which the engines validate."""
    if key_mode == "exact":
        raise ValueError(
            "key_mode='exact' routes through the key directory "
            "(ops/keydir.admit_slots), not the static slot map")
    if n_shards == 1 and key_mode == "hash":
        return slot_of(key, capacity)
    local = key if n_shards == 1 else key // n_shards
    return (local & (capacity // n_shards - 1)).astype("int32")


def key_row(key, capacity: int, key_mode: str = "direct",
            n_shards: int = 1):
    """Global table row of ``key``: owner block × local :func:`key_slot`."""
    owner = (key % n_shards).astype("int32")
    return owner * (capacity // n_shards) + key_slot(
        key, capacity, key_mode, n_shards)


def multi_hash(key: jnp.ndarray, depth: int, width: int) -> jnp.ndarray:
    """[B] keys (``[2, B]`` at 64 bits) → [depth, B] independent column
    indices in [0, width)."""
    assert width & (width - 1) == 0, "width must be a power of 2"
    cols = [
        (hash_key(key, seed=d) & jnp.uint32(width - 1)).astype(jnp.int32)
        for d in range(depth)
    ]
    return jnp.stack(cols, axis=0)
