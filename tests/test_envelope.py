"""Golden tests for the Debezium envelope codec (SURVEY §7 layer 1)."""

import base64

import numpy as np
import pytest

from real_time_fraud_detection_system_tpu.core.envelope import (
    decode_decimal_batch,
    decode_decimal_bytes,
    decode_transaction_envelopes,
    encode_decimal_cents,
    encode_transaction_envelope,
    encode_transaction_envelopes,
)


def test_decimal_golden_values():
    # Hand-computed big-endian signed encodings of DECIMAL(10,2) cents.
    golden = {
        0: b"\x00",
        1: b"\x01",
        127: b"\x7f",
        128: b"\x00\x80",
        256: b"\x01\x00",
        12345: b"\x30\x39",
        -1: b"\xff",
        -128: b"\x80",
        -129: b"\xff\x7f",
        99999999999: b"\x17\x48\x76\xe7\xff",
    }
    for cents, raw in golden.items():
        assert decode_decimal_bytes(raw) == cents
        assert base64.b64decode(encode_decimal_cents(cents)) == raw


def test_decimal_batch_matches_scalar(rng):
    cents = rng.integers(-(10**10), 10**10, size=500)
    raws = [base64.b64decode(encode_decimal_cents(c)) for c in cents]
    out = decode_decimal_batch(raws)
    assert np.array_equal(out, cents)


def test_decimal_batch_vectorized_edge_cases():
    """The packed-scatter decode is bit-identical to the scalar reference
    over every byte width 1..8, full-width int64 extremes, sign-bit
    boundaries, and degenerate inputs (empty batch / empty value)."""
    local = np.random.default_rng(1234)
    vals = [0, 1, -1, 127, 128, -128, -129, 255, -256,
            2**31 - 1, -(2**31), 2**62, -(2**62), 2**63 - 1, -(2**63)]
    # widths 1..8 at both sign-bit edges
    for w in range(1, 9):
        vals += [2 ** (8 * w - 1) - 1, -(2 ** (8 * w - 1))]
    vals += [int(v) for v in local.integers(-(2**62), 2**62, size=300)]
    raws = [base64.b64decode(encode_decimal_cents(v)) for v in vals]
    got = decode_decimal_batch(raws)
    want = np.array([decode_decimal_bytes(r) for r in raws], np.int64)
    assert np.array_equal(got, want)
    assert decode_decimal_batch([]).shape == (0,)
    assert decode_decimal_batch([b""])[0] == 0  # degenerate, not a crash
    try:
        decode_decimal_batch([b"\x00" * 9])
    except ValueError:
        pass
    else:
        raise AssertionError("9-byte decimal must raise")


def test_envelope_roundtrip(rng):
    n = 200
    tx_id = np.arange(n, dtype=np.int64)
    t_us = rng.integers(1_700_000_000, 1_800_000_000, n) * 1_000_000
    cust = rng.integers(0, 5000, n)
    term = rng.integers(0, 10000, n)
    cents = rng.integers(1, 10**7, n)
    msgs = encode_transaction_envelopes(tx_id, t_us, cust, term, cents)
    cols, invalid = decode_transaction_envelopes(msgs)
    assert not invalid.any()
    assert np.array_equal(cols["tx_id"], tx_id)
    assert np.array_equal(cols["tx_datetime_us"], t_us)
    assert np.array_equal(cols["customer_id"], cust)
    assert np.array_equal(cols["terminal_id"], term)
    assert np.array_equal(cols["tx_amount_cents"], cents)
    assert np.all(cols["op"] == 0)


def test_envelope_delete_and_tombstone():
    m_del = encode_transaction_envelope(7, 1_000_000, 1, 2, 500, op="d")
    tomb = b'{"schema": null, "payload": null}'
    junk = b"not json"
    cols, invalid = decode_transaction_envelopes([m_del, tomb, junk])
    assert invalid.tolist() == [False, True, True]
    assert cols["tx_id"][0] == 7 and cols["op"][0] == 2


@pytest.mark.parametrize("field, text", [
    # non-alphabet bytes: a lenient base64 decode drops them and yields a
    # garbage amount where native/envelope.cc rejects the row
    ("tx_amount", b'"A1oT{"'),
    # parses to inf: assigning it to an int64 column raised OverflowError
    # out of the whole poll
    ("tx_datetime", b"1e999"),
    ("tx_id", b"1" + b"0" * 30),  # an integer no int64 holds
    # a float is not an id: the strict parser took 19000000, the scanner 19
    ("customer_id", b"19E6"),
    ("terminal_id", b"true"),
])
def test_malformed_field_masks_the_row_not_the_poll(field, text):
    """One malformed envelope in a poll is masked; its neighbours decode."""
    good = encode_transaction_envelope(7, 1_000_000, 1, 2, 500)
    bad = encode_transaction_envelope(8, 2_000_000, 3, 4, 600)
    head, sep, tail = bad.partition(b'"%s":' % field.encode())
    end = min(i for i in (tail.find(b","), tail.find(b"}")) if i >= 0)
    bad = head + sep + text + tail[end:]
    cols, invalid = decode_transaction_envelopes([good, bad, good])
    assert invalid.tolist() == [False, True, False]
    assert cols["tx_id"].tolist() == [7, 0, 7]
    assert cols["tx_amount_cents"].tolist() == [500, 0, 500]
