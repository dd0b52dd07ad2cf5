"""C++ envelope decoder: exact parity with the Python reference decoder."""

import numpy as np
import pytest

from real_time_fraud_detection_system_tpu.core.envelope import (
    decode_transaction_envelopes,
    encode_transaction_envelope,
    encode_transaction_envelopes,
)
from real_time_fraud_detection_system_tpu.core.native import (
    decode_envelopes_slab,
    decode_transaction_envelopes_native,
    native_available,
)

pytestmark = pytest.mark.skipif(
    not native_available(), reason="g++ / native build unavailable"
)


def _corpus(rng, n):
    return encode_transaction_envelopes(
        np.arange(n, dtype=np.int64),
        rng.integers(1_700_000_000, 1_800_000_000, n) * 1_000_000,
        rng.integers(0, 5000, n),
        rng.integers(0, 10000, n),
        rng.integers(-(10**9), 10**10, n),
    )


def test_decode_workers_bit_identical(rng):
    """The multi-worker slab decode is the SAME columns as serial decode
    — worker count is a throughput knob, never a semantics knob. The
    corpus exceeds the parallel threshold so the pool path actually
    runs."""
    n = 10000
    msgs = _corpus(rng, n)
    ref_cols, ref_inv = decode_transaction_envelopes_native(
        msgs, workers=1)
    for w in (2, 3, 4, 8):
        cols, inv = decode_transaction_envelopes_native(msgs, workers=w)
        assert np.array_equal(ref_inv, inv), w
        for k in ref_cols:
            assert np.array_equal(ref_cols[k], cols[k]), (w, k)


def test_decode_slab_matches_whole_batch(rng):
    """Per-slab exactness: decoding [a, b) ranges of one packed buffer
    into slices of shared staging columns reproduces the whole-batch
    decode exactly, for uneven and degenerate split points."""
    n = 257
    msgs = _corpus(rng, n)
    ref_cols, ref_inv = decode_transaction_envelopes_native(
        msgs, workers=1)

    offsets = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(np.fromiter((len(m) for m in msgs), np.int64, count=n),
              out=offsets[1:])
    buf = b"".join(msgs)
    for bounds in ([0, n], [0, 1, n], [0, 100, 100, 256, n],
                   [0, 64, 128, 192, n]):
        outs = [np.zeros(n, np.int64) for _ in range(5)]
        outs += [np.zeros(n, np.int8), np.zeros(n, np.uint8)]
        for a, b in zip(bounds[:-1], bounds[1:]):
            decode_envelopes_slab(buf, offsets, a, b, *outs)
        tx_id, t_us, cust, term, cents, op, valid = outs
        assert np.array_equal(ref_cols["tx_id"], tx_id), bounds
        assert np.array_equal(ref_cols["tx_datetime_us"], t_us), bounds
        assert np.array_equal(ref_cols["customer_id"], cust), bounds
        assert np.array_equal(ref_cols["terminal_id"], term), bounds
        assert np.array_equal(ref_cols["tx_amount_cents"], cents), bounds
        assert np.array_equal(ref_cols["op"], op), bounds
        assert np.array_equal(ref_inv, valid == 0), bounds


def test_decode_worker_config_and_slab_metric(rng):
    from real_time_fraud_detection_system_tpu.core import native
    from real_time_fraud_detection_system_tpu.utils.metrics import (
        get_registry,
    )

    before = native.get_decode_workers()
    try:
        assert native.set_decode_workers(3) == 3
        assert native.get_decode_workers() == 3
        g = get_registry().get("rtfds_decode_workers")
        assert g is not None and g.value == 3
        h = get_registry().histogram("rtfds_decode_slab_seconds")
        c0 = h.count
        # above the parallel threshold: one slab per worker
        msgs = _corpus(rng, 8192)
        decode_transaction_envelopes_native(msgs)
        assert h.count == c0 + 3
        # below it: exactly one (serial) slab
        decode_transaction_envelopes_native(msgs[:10])
        assert h.count == c0 + 4
    finally:
        native.set_decode_workers(before)


def test_native_parity_random(rng):
    n = 5000
    msgs = encode_transaction_envelopes(
        np.arange(n, dtype=np.int64),
        rng.integers(1_700_000_000, 1_800_000_000, n) * 1_000_000,
        rng.integers(0, 5000, n),
        rng.integers(0, 10000, n),
        rng.integers(-(10**9), 10**10, n),
    )
    c_py, i_py = decode_transaction_envelopes(msgs)
    c_nat, i_nat = decode_transaction_envelopes_native(msgs)
    assert np.array_equal(i_py, i_nat)
    for k in c_py:
        assert np.array_equal(c_py[k], c_nat[k]), k


def test_native_parity_malformed():
    cases = [
        encode_transaction_envelope(1, 2, 3, 4, 500),
        encode_transaction_envelope(7, 8, 9, 10, -12345, op="d"),
        encode_transaction_envelope(11, 12, 13, 14, 0, op="u"),
        b"junk",
        b"",
        b'{"payload": null}',
        b'{"payload": {"after": null, "before": null}}',
        b'{"no_payload": 1}',
        # whitespace variants
        b'{ "payload" : { "after" : { "tx_id" : 5, "tx_datetime": 6,'
        b' "customer_id": 7, "terminal_id": 8, "tx_amount": "e A=" } } }'
        .replace(b"e A=", b"eA=="),
    ]
    c_py, i_py = decode_transaction_envelopes(cases)
    c_nat, i_nat = decode_transaction_envelopes_native(cases)
    assert np.array_equal(i_py, i_nat)
    for k in ("tx_id", "tx_datetime_us", "tx_amount_cents", "op"):
        assert np.array_equal(c_py[k], c_nat[k]), (k, c_py[k], c_nat[k])


def test_native_schema_section_does_not_confuse_scanner():
    # The Debezium wire format includes a "schema" section that also contains
    # the strings "after"/"op" etc. — the scanner must find payload's keys.
    msg = (
        b'{"schema": {"fields": [{"field": "after", "op": "x", "payload": 1}]},'
        b' "payload": {"before": null, "after": {"tx_id": 42,'
        b' "tx_datetime": 99, "customer_id": 1, "terminal_id": 2,'
        b' "tx_amount": "Aci0"}, "op": "c"}}'
    )
    c, inv = decode_transaction_envelopes_native([msg])
    assert not inv[0]
    assert c["tx_id"][0] == 42
    assert c["tx_amount_cents"][0] == 0x01C8B4


@pytest.mark.parametrize("seed", [0, 1, 23, 35, 50, 108])
def test_native_parity_differential_fuzz(seed):
    """Mutation fuzz pinning the decoders' validity contract (see
    core/native.py docstring): the scanner is strictly more lenient — its
    invalid set is a SUBSET of the strict parser's — and wherever both
    accept a message the decoded columns are bit-identical. Inputs:
    truncations, byte flips, garbage splices, whitespace injection.

    Its own generator on fixed seeds, so what it draws does not depend on
    which tests ran before it. Of seeds 0..199 the strict parser once
    failed 54: a lenient base64 decode accepted a mangled amount the
    scanner rejects (1, 23), an id of ``1e999`` raised OverflowError out
    of the whole poll (35, 50), and a float id (``19E6``) decoded to
    another number than the scanner's (108)."""
    rng = np.random.default_rng(seed)
    base = encode_transaction_envelopes(
        np.arange(64, dtype=np.int64),
        rng.integers(1_700_000_000, 1_800_000_000, 64) * 1_000_000,
        rng.integers(0, 5000, 64),
        rng.integers(0, 10000, 64),
        rng.integers(-(10**6), 10**6, 64),
    )
    garbage = [b"", b"{", b"}", b'\x00\xff\xfe', b'{"payload":',
               b'[1,2,3]', b'true', b'"payload"']
    cases = []
    for i in range(400):
        m = bytearray(base[int(rng.integers(0, len(base)))])
        op = int(rng.integers(0, 5))
        if op == 0 and len(m) > 2:  # truncate
            m = m[: int(rng.integers(1, len(m)))]
        elif op == 1 and len(m) > 4:  # flip random bytes
            for _ in range(int(rng.integers(1, 4))):
                m[int(rng.integers(0, len(m)))] = int(rng.integers(32, 127))
            # keep it bytes-decodable; arbitrary flips within ASCII range
        elif op == 2:  # splice garbage into the middle
            pos = int(rng.integers(0, len(m)))
            g = garbage[int(rng.integers(0, len(garbage)))]
            m = m[:pos] + bytearray(g) + m[pos:]
        elif op == 3:  # random whitespace injection around punctuation
            out = bytearray()
            for b in m:
                out.append(b)
                if b in b'{},:' and rng.random() < 0.3:
                    out += b" \t"
            m = out
        # op == 4: leave valid (control group)
        cases.append(bytes(m))
    cases += garbage

    c_py, i_py = decode_transaction_envelopes(cases)
    c_nat, i_nat = decode_transaction_envelopes_native(cases)
    # Strictness ordering: scanner-invalid ⊆ parser-invalid. A message the
    # lenient scanner drops but the strict parser accepts would be silent
    # row loss on the native path — never allowed.
    leak = i_nat & ~i_py
    assert not leak.any(), (
        f"scanner rejected messages the strict parser accepts: "
        f"{np.flatnonzero(leak)[:5]}"
    )
    both_ok = ~i_py & ~i_nat
    for k in c_py:
        ok = np.array_equal(c_py[k][both_ok], c_nat[k][both_ok])
        assert ok, (k, np.flatnonzero(
            c_py[k][both_ok] != c_nat[k][both_ok])[:5])
    # Control group sanity: some mutated-but-intact and all clean cases
    # must decode on both paths.
    assert both_ok.sum() > 50


def test_hostprep_latest_wins_matches_numpy_fuzz():
    """C++ hash dedup ≡ ops.dedup.latest_wins_mask_np, incl. ts ties
    (later position wins) and heavy duplication."""
    from real_time_fraud_detection_system_tpu.core import native
    from real_time_fraud_detection_system_tpu.ops.dedup import (
        latest_wins_mask_np,
    )

    if not native.hostprep_available():
        pytest.skip("native hostprep unavailable")
    rng = np.random.default_rng(17)
    for _ in range(30):
        n = int(rng.integers(1, 4000))
        tx = rng.integers(0, max(1, n // 3), n)  # heavy duplicates
        ts = rng.integers(0, 20, n)  # many ties
        np.testing.assert_array_equal(
            native.latest_wins_keep(tx, ts),
            latest_wins_mask_np(tx, ts))


@pytest.mark.parametrize("key_bits", [32, 64])
def test_hostprep_pack_rows_bitexact_fuzz(key_bits):
    """C++ fused pack ≡ make_batch + pack_batch bit-for-bit (key folds —
    at ``key_bits=64`` the two words of every id, negative ids as their
    bit patterns — floor day/tod split, cents→f32, labels, zero
    padding)."""
    from real_time_fraud_detection_system_tpu.core import native
    from real_time_fraud_detection_system_tpu.core.batch import (
        make_batch,
        pack_batch,
    )

    if not native.hostprep_available():
        pytest.skip("native hostprep unavailable")
    rng = np.random.default_rng(23)
    for trial in range(20):
        n = int(rng.integers(1, 3000))
        dt = rng.integers(0, 2**45, n)
        cu = rng.integers(-2**63, 2**63 - 1, n)
        te = rng.integers(0, 2**63 - 1, n)
        cu[:3] = (-1, 0, 2**32)[:min(3, n)]
        am = rng.integers(0, 10**9, n)
        lab = rng.integers(-1, 2, n) if trial % 2 else None
        pad = int(n + rng.integers(0, 64))
        ref = pack_batch(make_batch(cu, te, dt, am, label=lab,
                                    pad_to=pad, key_bits=key_bits))
        got = native.pack_rows(dt, cu, te, am, lab, pad, key_bits)
        assert got.shape == (9 if key_bits == 64 else 7, pad)
        np.testing.assert_array_equal(got, ref, err_msg=f"trial {trial}")


def test_hostprep_engine_parity_native_vs_numpy(monkeypatch):
    """The engine produces identical results whether the native host-prep
    path or the NumPy fallback runs."""
    import jax.numpy as jnp

    from real_time_fraud_detection_system_tpu.config import (
        Config,
        FeatureConfig,
        RuntimeConfig,
    )
    from real_time_fraud_detection_system_tpu.core import native
    from real_time_fraud_detection_system_tpu.models.logreg import (
        init_logreg,
    )
    from real_time_fraud_detection_system_tpu.models.scaler import Scaler
    from real_time_fraud_detection_system_tpu.runtime.engine import (
        ScoringEngine,
    )

    if not native.hostprep_available():
        pytest.skip("native hostprep unavailable")
    cfg = Config(
        features=FeatureConfig(customer_capacity=128,
                               terminal_capacity=256),
        runtime=RuntimeConfig(batch_buckets=(256,), max_batch_rows=256),
    )
    rng = np.random.default_rng(3)
    n = 200
    batch = {
        "tx_id": np.concatenate([np.arange(n - 20), np.arange(20)]),
        "tx_datetime_us": np.sort(
            rng.integers(0, 5 * 86_400_000_000, n)).astype(np.int64),
        "customer_id": rng.integers(0, 60, n),
        "terminal_id": rng.integers(0, 90, n),
        "tx_amount_cents": rng.integers(100, 10**6, n),
        "kafka_ts_ms": np.arange(n, dtype=np.int64),
    }

    def run():
        eng = ScoringEngine(
            cfg, kind="logreg", params=init_logreg(15),
            scaler=Scaler(mean=jnp.zeros(15), scale=jnp.ones(15)))
        return eng.process_batch(dict(batch))

    r_nat = run()
    monkeypatch.setattr(native, "hostprep_available", lambda: False)
    r_np = run()
    np.testing.assert_array_equal(r_nat.tx_id, r_np.tx_id)
    np.testing.assert_array_equal(r_nat.probs, r_np.probs)
    np.testing.assert_array_equal(r_nat.features, r_np.features)


def test_hostprep_sentinel_key_parity():
    """tx_id == INT64_MIN doubles as the NumPy mask's invalid sentinel
    and is dropped there — the native path must match."""
    from real_time_fraud_detection_system_tpu.core import native
    from real_time_fraud_detection_system_tpu.ops.dedup import (
        latest_wins_mask_np,
    )

    if not native.hostprep_available():
        pytest.skip("native hostprep unavailable")
    lo = np.iinfo(np.int64).min
    tx = np.array([5, lo, 5, lo, 7], dtype=np.int64)
    ts = np.array([1, 2, 3, 4, 5], dtype=np.int64)
    got = native.latest_wins_keep(tx, ts)
    np.testing.assert_array_equal(got, latest_wins_mask_np(tx, ts))
    assert not got[1] and not got[3]
