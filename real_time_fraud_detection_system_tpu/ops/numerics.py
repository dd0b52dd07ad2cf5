"""f32 division that rounds the way IEEE 754 (and NumPy, and sklearn) do.

The TPU's f32 divide is a reciprocal plus refinement, not a correctly
rounded quotient. Measured on a v5e (PR 21's chip runs): with run-time
operands XLA's divide differs from NumPy's in 32% of results by 1 ulp,
Mosaic's (inside a Pallas kernel) in 40% by up to 2 ulps, and the two
differ from each other in 25%. That breaks two contracts the CPU tests
pin: the device's standardized features equal the host oracle's (a
1-ulp-different input sitting on a split threshold flips that tree's vote
— `chip_smoke.py` saw probabilities off by exactly 1/T against
``--scorer cpu``), and the fused Pallas kernels emit features bit-identical
to the XLA composition's.

:func:`div_ieee` repairs the quotient with one correction step whose
residual is computed exactly (Dekker's two-product on Veltkamp-split
halves: only IEEE multiplies, adds and subtracts, which the VPU does round
correctly). Same chip runs: 0 of 16.7M quotients differ from NumPy's, in
XLA and in Mosaic. On the CPU the first quotient is already correctly
rounded and the correction adds nothing, so results there are unchanged
(where XLA has turned a divide by a compile-time constant into a
reciprocal multiply — it does, on any backend — the correction repairs
that as well).
About 25 VPU flops per element, on [B, 15] and [B, 6] blocks next to the
[B, T, I] tree contraction.

:func:`sum_fixed_order` is the other half of the same contract. An f32
``reduce`` has no defined order: the same ``jnp.sum`` in two different
programs (one chip vs the mesh, XLA vs Mosaic) is tiled differently and
rounds differently (same chip runs: one-chip and sharded probabilities
differed in the last bit for rows whose 15 features were bit-equal).
Written as explicit adds, the order is part of the program.
"""

from __future__ import annotations

import jax.numpy as jnp

_SPLIT = 4097.0  # 2^12 + 1: Veltkamp's constant for a 24-bit significand


def _split(a):
    c = a * jnp.float32(_SPLIT)
    hi = c - (c - a)
    return hi, a - hi


def div_ieee(a, b):
    """``a / b`` in f32, correctly rounded on every backend for finite
    quotients (|a|, |b| below ~8e34, so the split cannot overflow —
    feature values and scaler stddevs are nowhere near). Where the
    correction is not finite — ``x / 0``, ``0 / 0``, non-finite operands —
    the plain quotient stands, so ±inf and NaN mean what they meant (the
    nan-guard's quarantine keys on them)."""
    q = a / b
    p = q * b
    qh, ql = _split(q)
    bh, bl = _split(b)
    # p + err == q * b exactly; a - p is exact (p is within an ulp of a)
    err = ((qh * bh - p) + qh * bl + ql * bh) + ql * bl
    fixed = q + ((a - p) - err) / b
    return jnp.where(jnp.abs(fixed) < jnp.inf, fixed, q)


def sum_fixed_order(x, axis: int = -1, keepdims: bool = False):
    """``x.sum(axis)`` as an explicit balanced tree of adds: the axis is
    zero-padded to a power of two and its upper half added onto its lower
    half until one element is left. No compiler reassociates explicit f32
    adds, so two programs that call this on equal inputs get bit-equal
    sums, whatever their batch size, sharding or backend. log2(n) adds of
    shrinking width: for the 40 day buckets and the 100 trees this is
    noise next to the gathers and contractions that feed it."""
    x = jnp.moveaxis(x, axis, -1)
    n = x.shape[-1]
    p = 1 << max(n - 1, 0).bit_length()
    if p != n:
        x = jnp.concatenate(
            [x, jnp.zeros(x.shape[:-1] + (p - n,), x.dtype)], axis=-1)
    while p > 1:
        p //= 2
        x = x[..., :p] + x[..., p:]
    return jnp.moveaxis(x, -1, axis) if keepdims else x[..., 0]
