"""64-bit keys (``FeatureConfig.key_bits=64``): the host splits an id into
its two words instead of folding it, the batch, the step, the sketches and
the directory carry both, and two ids are one key only if all 64 bits
agree — held against the plain reference and against a ``direct`` engine
on seeded ids of card-number width with PLANTED equal-fold pairs, which
the same run at ``key_bits=32`` gets wrong. And the other half of the
contract: at 32 the batch, the state and the inventory are the parent's,
and every path that cannot carry a wide key refuses it by name."""

import dataclasses as dc
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from real_time_fraud_detection_system_tpu.config import (
    Config,
    FeatureConfig,
    RuntimeConfig,
)
from real_time_fraud_detection_system_tpu.core.batch import (
    TxBatch,
    device_keys,
    fold_key,
    host_keys,
    join_key,
    make_batch,
    pack_batch,
    packed_rows,
    pad_batch,
    split_key,
    unpack_batch,
    wide_id_rows,
)
from real_time_fraud_detection_system_tpu.features.offline import (
    pandas_rolling_features,
)
from real_time_fraud_detection_system_tpu.features.online import (
    init_feature_state,
    state_bytes,
)
from real_time_fraud_detection_system_tpu.models.logreg import init_logreg
from real_time_fraud_detection_system_tpu.models.scaler import Scaler
from real_time_fraud_detection_system_tpu.ops.cms import (
    cms_init,
    cms_query,
    cms_update,
)
from real_time_fraud_detection_system_tpu.ops.hashing import (
    hash_key,
    hash_u32,
    multi_hash,
)
from real_time_fraud_detection_system_tpu.runtime.engine import (
    ScoringEngine,
)
from real_time_fraud_detection_system_tpu.utils.metrics import (
    MetricsRegistry,
)

DAY0 = 20200
SLOTS = dict(customer_capacity=128, terminal_capacity=256,
             cms_width=1 << 12)


def planted(rng, n, lo=10 ** 15, hi=10 ** 16):
    """2n ids of ``[lo, hi)``: n seeded draws and a fold twin of each —
    the same bits flipped in both words, so ``fold_key`` cannot tell a
    pair apart and the ids differ."""
    base = rng.integers(lo, hi, size=n, dtype=np.int64)
    m = rng.integers(1, 1 << 20, size=n).astype(np.uint64)
    twin = (base.view(np.uint64) ^ m ^ (m << np.uint64(32))).view(np.int64)
    assert (fold_key(twin) == fold_key(base)).all()
    assert (twin != base).all() and (twin >= 1 << 32).all()
    return np.concatenate([base, twin])


class _Heard(logging.Handler):
    """What the engine's logger said, while attached (the ``rtfds``
    loggers do not propagate to pytest's ``caplog``)."""

    def __init__(self):
        super().__init__(logging.WARNING)
        self.said = []

    def emit(self, record):
        self.said.append(record.getMessage())

    def __enter__(self):
        from real_time_fraud_detection_system_tpu.utils import get_logger

        self._log = get_logger("engine")
        self._log.addHandler(self)
        return self

    def __exit__(self, *exc):
        self._log.removeHandler(self)


def _engine(cfg, reg=None):
    return ScoringEngine(
        cfg, kind="logreg", params=init_logreg(15),
        scaler=Scaler(mean=np.zeros(15, np.float32),
                      scale=np.ones(15, np.float32)),
        metrics=reg if reg is not None else MetricsRegistry())


START = "2025-04-01"
START_S = 1_743_465_600  # START at 00:00 UTC, seconds since the epoch


def _stream(rng, cust, term, n_batches=12, n=150):
    """Batches of columns over the planted ids — batch b is day b, every
    row at its noon, so a trailing wall-clock window of w days and the
    calendar days [d - w + 1, d] hold the same rows, batch-mates included
    — and the same batches over the ids' dense ranks (what a ``direct``
    engine can hold)."""
    wide, dense = [], []
    for b in range(n_batches):
        us = np.full(n, (START_S + b * 86400 + 43200) * 1_000_000, np.int64)
        ci, ti = (rng.integers(0, len(cust), n),
                  rng.integers(0, len(term), n))
        cols = {"tx_id": np.arange(b * n, (b + 1) * n, dtype=np.int64),
                "tx_datetime_us": us,
                "tx_amount_cents": rng.integers(100, 50000, n).astype(
                    np.int64),
                "kafka_ts_ms": us // 1000}
        wide.append(dict(cols, customer_id=cust[ci], terminal_id=term[ti]))
        dense.append(dict(cols, customer_id=ci.astype(np.int64),
                          terminal_id=ti.astype(np.int64)))
    return wide, dense


def test_wide_ids_equal_the_reference_and_direct_and_32_bits_gets_them_wrong():
    """The test that would have caught the limit. An engine run at
    ``key_bits=64`` on ids drawn from [10^15, 10^16) with planted
    equal-fold pairs: its 15 features equal the plain reference's (a
    pandas groupby over the int64 ids) and, to the bit, a ``direct``
    engine's on the ids' dense ranks; zero recompiles with every program
    compiled ahead, through three compactions. The same run at
    ``key_bits=32`` merges the pairs — and says so, once."""
    from real_time_fraud_detection_system_tpu.data.generator import (
        Transactions,
    )
    from real_time_fraud_detection_system_tpu.features.spec import (
        FEATURE_NAMES,
    )

    rng = np.random.default_rng(41)
    cust, term = planted(rng, 20), planted(rng, 40, 1 << 32, 1 << 62)
    wide, dense = _stream(rng, cust, term)
    rt = RuntimeConfig(batch_buckets=(64, 256), max_batch_rows=256,
                       precompile=True)
    exact = dict(key_mode="exact", compact_every=3, keydir_probes=16,
                 **SLOTS)
    reg64, reg32 = MetricsRegistry(), MetricsRegistry()
    e64 = _engine(Config(features=FeatureConfig(key_bits=64, **exact),
                         runtime=rt), reg64)
    e32 = _engine(Config(features=FeatureConfig(**exact), runtime=rt),
                  reg32)
    direct = _engine(Config(features=FeatureConfig(**SLOTS), runtime=rt))
    assert ("step", 9, 256) in [s.key for s in e64.dispatch_inventory()]
    e64.precompile()
    got, narrow = [], []
    with _Heard() as heard:
        for w, d in zip(wide, dense):
            a, b, c = (e64.process_batch(dict(w)),
                       e32.process_batch(dict(w)),
                       direct.process_batch(dict(d)))
            np.testing.assert_array_equal(a.features, c.features)
            np.testing.assert_array_equal(a.probs, c.probs)
            np.testing.assert_array_equal(a.customer_id, w["customer_id"])
            got.append(a.features)
            narrow.append(b.features)
    got, narrow = np.concatenate(got), np.concatenate(narrow)
    # the plain reference: a groupby over the int64 ids themselves
    cols = {k: np.concatenate([w[k] for w in wide]) for k in wide[0]}
    secs = cols["tx_datetime_us"] // 1_000_000 - START_S
    ref = pandas_rolling_features(Transactions(
        tx_id=cols["tx_id"], tx_time_seconds=secs,
        tx_time_days=(secs // 86400).astype(np.int32),
        customer_id=cols["customer_id"], terminal_id=cols["terminal_id"],
        amount_cents=cols["tx_amount_cents"],
        tx_fraud=np.zeros(len(secs), np.int8),
        tx_fraud_scenario=np.zeros(len(secs), np.int8)), start_date=START)
    # The reference's window ends at the row itself; the program's holds
    # the row's batch-mates of that day too (update, then query). They
    # agree on a customer's LAST row of a day, where both have seen the
    # day whole; the terminal windows lie 7 days back and agree on all.
    day = secs // 86400
    order = np.lexsort((np.arange(len(day)), day, cols["customer_id"]))
    ends = np.r_[np.flatnonzero(
        (np.diff(cols["customer_id"][order]) != 0)
        | (np.diff(day[order]) != 0)), len(order) - 1]
    last = order[ends]
    counts = [i for i, n in enumerate(FEATURE_NAMES) if "NB_TX" in n]
    c_counts = [i for i in counts if "CUSTOMER" in FEATURE_NAMES[i]]
    t_counts = [i for i in counts if "TERMINAL" in FEATURE_NAMES[i]]
    avgs = [i for i, n in enumerate(FEATURE_NAMES) if "AVG" in n]
    assert len(last) > 400
    np.testing.assert_array_equal(got[:, t_counts], ref[:, t_counts])
    np.testing.assert_array_equal(got[last][:, c_counts],
                                  ref[last][:, c_counts])
    np.testing.assert_allclose(got[last][:, avgs], ref[last][:, avgs],
                               rtol=2e-6)
    np.testing.assert_allclose(got[:, :3], ref[:, :3], rtol=1e-6)
    # ... which the 32-bit deployment gets wrong: a card served on its
    # twin's history, a terminal on another merchant's, in the counts
    assert int((narrow[last][:, c_counts] != ref[last][:, c_counts]
                ).sum()) > 100
    assert int((narrow[:, t_counts] != ref[:, t_counts]).sum()) > 100
    # no program compiled in the stream, the sketch tier served no row
    assert reg64.get("rtfds_xla_recompiles_total").value == 0
    assert reg64.get("rtfds_aot_fallbacks_total").value == 0
    assert reg64.get("rtfds_feature_tier_rows_total", tier="cms").value == 0
    assert reg64.get("rtfds_state_compactions_total").value >= 3
    # the planted pairs were met under their shared fingerprints
    assert reg64.get("rtfds_keydir_alias_rows_total").value > 0
    # every row's id is past 32 bits, and both widths count that
    for reg in (reg64, reg32):
        assert reg.get("rtfds_wide_id_rows_total").value == 12 * 150
    said = [m for m in heard.said if "does not fit 32 bits" in m]
    assert len(said) == 1 and "--key-bits 64" in said[0]


def test_serial_ids_count_no_wide_row_and_warn_nothing():
    rng = np.random.default_rng(2)
    wide, dense = _stream(rng, np.arange(40), np.arange(80), n_batches=2)
    del wide
    reg = MetricsRegistry()
    eng = _engine(Config(features=FeatureConfig(**SLOTS),
                         runtime=RuntimeConfig(batch_buckets=(256,),
                                               max_batch_rows=256)), reg)
    with _Heard() as heard:
        for d in dense:
            eng.process_batch(d)
    assert reg.get("rtfds_wide_id_rows_total").value == 0
    assert not [m for m in heard.said if "32 bits" in m]
    assert wide_id_rows(np.asarray([1, -1, 1 << 32], np.int64),
                        np.asarray([2, 3, 4], np.int64)) == 2
    assert wide_id_rows(np.asarray([], np.int64),
                        np.asarray([], np.int64)) == 0


# -- the batch: split, not folded ------------------------------------------

EDGE_IDS = np.asarray(
    [0, 1, (1 << 32) - 1, 1 << 32, (1 << 63) - 1, -1, -2, -(1 << 63),
     10 ** 15, 9_999_999_999_999_999], np.int64)


def test_split_and_join_are_inverse_and_negatives_are_bit_patterns():
    words = split_key(EDGE_IDS)
    assert words.shape == (2, len(EDGE_IDS)) and words.dtype == np.uint32
    np.testing.assert_array_equal(join_key(words),
                                  EDGE_IDS.view(np.uint64))
    assert tuple(words[:, 5]) == (0xFFFFFFFF, 0xFFFFFFFF)  # int64 -1
    assert tuple(words[:, 7]) == (0, 0x80000000)  # int64 min
    np.testing.assert_array_equal(words[0] ^ words[1], fold_key(EDGE_IDS))
    np.testing.assert_array_equal(host_keys(EDGE_IDS, 64),
                                  EDGE_IDS.view(np.uint64))
    np.testing.assert_array_equal(host_keys(EDGE_IDS, 32),
                                  fold_key(EDGE_IDS))
    np.testing.assert_array_equal(device_keys(host_keys(EDGE_IDS, 64)),
                                  words)
    np.testing.assert_array_equal(device_keys(host_keys(EDGE_IDS, 32)),
                                  fold_key(EDGE_IDS))


def _columns(rng, n):
    return dict(
        customer_id=rng.integers(-(1 << 63), (1 << 63) - 1, n,
                                 dtype=np.int64),
        terminal_id=rng.integers(-(1 << 63), (1 << 63) - 1, n,
                                 dtype=np.int64),
        tx_datetime_us=rng.integers(0, 2_000_000_000, n).astype(np.int64)
        * 1_000_000,
        amount_cents=rng.integers(1, 10 ** 7, n).astype(np.int64),
        label=rng.integers(-1, 2, n).astype(np.int64))


def test_wide_batch_packs_nine_rows_and_unpacks_to_both_words():
    rng = np.random.default_rng(5)
    cols = _columns(rng, 50)
    batch = make_batch(pad_to=64, key_bits=64, **cols)
    assert batch.customer_key.shape == (2, 64) and batch.size == 64
    np.testing.assert_array_equal(batch.customer_key[:, :50],
                                  split_key(cols["customer_id"]))
    assert not batch.customer_key[:, 50:].any()  # the padding
    packed = pack_batch(batch)
    assert packed.shape == (packed_rows(64), 64) == (9, 64)
    narrow = pack_batch(make_batch(pad_to=64, **cols))
    assert narrow.shape == (packed_rows(32), 64) == (7, 64)
    np.testing.assert_array_equal(packed[2:7], narrow[2:7])
    back = jax.jit(unpack_batch)(jnp.asarray(packed))
    for f in TxBatch._fields:
        np.testing.assert_array_equal(np.asarray(getattr(back, f)),
                                      np.asarray(getattr(batch, f)))
    wider = pad_batch(batch, 128)
    assert wider.terminal_key.shape == (2, 128) and wider.size == 128
    np.testing.assert_array_equal(wider.terminal_key[:, :64],
                                  batch.terminal_key)


def test_the_32_bit_batch_state_and_inventory_are_the_parents():
    """At ``key_bits=32`` (the default) what the device sees has the
    parent's leaves, shapes and dtypes: the packed batch, ``TxBatch``, the
    feature state's pytree, the dispatch keys — and the exact
    configuration's state is still 8,409,579,848 bytes."""
    rng = np.random.default_rng(6)
    batch = make_batch(pad_to=64, **_columns(rng, 50))
    assert [(f, np.asarray(x).shape, str(np.asarray(x).dtype))
            for f, x in zip(TxBatch._fields, batch)] == [
        ("customer_key", (64,), "uint32"), ("terminal_key", (64,), "uint32"),
        ("day", (64,), "int32"), ("tod_s", (64,), "int32"),
        ("amount", (64,), "float32"), ("label", (64,), "int32"),
        ("valid", (64,), "bool")]
    packed = pack_batch(batch)
    assert packed.shape == (7, 64) and packed.dtype == np.int32
    exact = FeatureConfig(key_mode="exact", keydir_probes=16,
                          compact_every=64, customer_capacity=1 << 22,
                          terminal_capacity=1 << 23)
    assert exact.key_bits == 32
    assert state_bytes(exact)["total"] == 8_409_579_848
    assert state_bytes(dc.replace(exact, key_bits=64)) == {
        "dense": 8_053_063_680, "directory": 452_984_840,
        "cms": 104_857_920, "total": 8_610_906_440}
    small = FeatureConfig(key_mode="exact", **SLOTS)
    leaves = jax.tree_util.tree_flatten_with_path(
        init_feature_state(small))[0]
    names = [jax.tree_util.keystr(p) for p, _ in leaves]
    assert len(leaves) == 23 and not [n for n in names if "keys_" in n]
    wide = jax.tree_util.tree_flatten_with_path(
        init_feature_state(dc.replace(small, key_bits=64)))[0]
    assert [jax.tree_util.keystr(p) for p, _ in wide
            if "keys_" in jax.tree_util.keystr(p)] == [
        ".customer_dir.keys_lo", ".customer_dir.keys_hi",
        ".terminal_dir.keys_lo", ".terminal_dir.keys_hi"]
    assert len(wide) == 27
    eng = _engine(Config(features=small, runtime=RuntimeConfig(
        batch_buckets=(64, 256), max_batch_rows=256)))
    assert [s.key for s in eng.dispatch_inventory()] == [
        ("step", 7, 64), ("step", 7, 256)]
    (sig,) = [s for s in eng.dispatch_inventory() if s.bucket == 64]
    assert eng.signature_templates(sig)[3].shape == (7, 64)


# -- hashing and the sketches: both words are mixed --------------------------

def test_wide_hash_mixes_both_words_and_leaves_the_narrow_hash_alone():
    rng = np.random.default_rng(8)
    one = jnp.asarray(rng.integers(0, 1 << 32, 4096, dtype=np.uint32))
    np.testing.assert_array_equal(np.asarray(hash_key(one, 3)),
                                  np.asarray(hash_u32(one, 3)))
    ids = planted(rng, 2048)
    words = jnp.asarray(split_key(ids))
    h = np.asarray(hash_key(words, 0))
    # fold twins share nothing: no pair of the 2,048 hashes alike, and
    # neither word alone decides the hash
    assert (h[:2048] != h[2048:]).all()
    lo_only = np.asarray(hash_key(words.at[1].set(0), 0))
    hi_only = np.asarray(hash_key(words.at[0].set(0), 0))
    assert (h != lo_only).mean() > 0.99 and (h != hi_only).mean() > 0.99
    cols = np.asarray(multi_hash(words, 4, 1 << 12))
    assert cols.shape == (4, 4096)
    # twins share a sketch cell no more often than any two keys: of 4 x
    # 2,048 (depth, pair) cells, 2 alike in expectation
    assert (cols[:, :2048] == cols[:, 2048:]).sum() < 12


def test_the_sketch_keeps_fold_twins_apart():
    rng = np.random.default_rng(9)
    ids = planted(rng, 8)
    words = jnp.asarray(split_key(ids))
    day = jnp.full(16, DAY0, jnp.int32)
    amount = jnp.arange(1, 17, dtype=jnp.float32)
    only_base = jnp.asarray(np.arange(16) < 8)
    sk = cms_update(cms_init(4, 1 << 12, 40), words, amount, day, only_base)
    count, total = cms_query(sk, words, day, (1,))
    np.testing.assert_array_equal(np.asarray(count)[:, 0],
                                  np.asarray(only_base, np.float32))
    np.testing.assert_array_equal(
        np.asarray(total)[:, 0], np.where(np.arange(16) < 8,
                                          np.asarray(amount), 0.0))


# -- the refusals: no path folds a wide id silently --------------------------

def test_key_bits_is_32_or_64_and_64_needs_the_directory():
    with pytest.raises(ValueError, match="key_bits must be 32 or 64"):
        FeatureConfig(key_bits=48)
    for mode in ("direct", "hash"):
        with pytest.raises(ValueError, match="key_bits=64 requires"):
            FeatureConfig(key_bits=64, key_mode=mode)
    FeatureConfig(key_bits=64, key_mode="exact")
    FeatureConfig(key_bits=64, key_mode="exact", customer_source="cms")


def test_the_mesh_the_sequence_scorer_and_the_stacked_state_refuse_64():
    from real_time_fraud_detection_system_tpu.runtime import (
        ShardedScoringEngine,
    )

    cfg = Config(features=FeatureConfig(key_mode="exact", key_bits=64,
                                        **SLOTS),
                 runtime=RuntimeConfig(batch_buckets=(64,),
                                       max_batch_rows=64))
    kw = dict(kind="logreg", params=init_logreg(15),
              scaler=Scaler(mean=np.zeros(15, np.float32),
                            scale=np.ones(15, np.float32)),
              metrics=MetricsRegistry())
    with pytest.raises(ValueError, match="key_bits=64 is not wired for "
                                         "the sharded engine"):
        ShardedScoringEngine(cfg, n_devices=2, rows_per_shard=32, **kw)
    with pytest.raises(ValueError, match="key_bits=64 has no sharded "
                                         "layout"):
        init_feature_state(cfg.features, n_shards=2)
    with pytest.raises(ValueError, match="key_bits=64"):
        ScoringEngine(cfg, **dict(kw, kind="sequence", params=None))


def test_the_cli_refuses_a_width_it_does_not_serve(capsys):
    from real_time_fraud_detection_system_tpu import cli

    with pytest.raises(SystemExit):
        cli.main(["score", "--key-bits", "48"])
    assert "--key-bits" in capsys.readouterr().err


# -- the reserved pattern: served from the sketch, and counted ---------------

def test_a_row_that_carries_the_reserved_pattern_is_counted_not_merged():
    rng = np.random.default_rng(10)
    reg = MetricsRegistry()
    eng = _engine(Config(
        features=FeatureConfig(key_mode="exact", key_bits=64,
                               keydir_probes=16, **SLOTS),
        runtime=RuntimeConfig(batch_buckets=(64,), max_batch_rows=64)), reg)
    n = 40
    us = (DAY0 * 86400 + rng.integers(0, 86400, n)).astype(
        np.int64) * 1_000_000
    cust = rng.integers(10 ** 15, 10 ** 16, n, dtype=np.int64)
    cust[:3] = -1  # 0xFFFFFFFF_FFFFFFFF three times
    cust[3] = -2  # its neighbour: an id like any other
    res = eng.process_batch({
        "tx_id": np.arange(n, dtype=np.int64), "tx_datetime_us": us,
        "customer_id": cust,
        "terminal_id": rng.integers(1 << 32, 1 << 62, n, dtype=np.int64),
        "tx_amount_cents": np.full(n, 1000, np.int64),
        "kafka_ts_ms": us // 1000})
    # three (row x keyspace) reads came from the sketch tier: visible
    assert reg.get("rtfds_feature_tier_rows_total", tier="cms").value == 3
    assert reg.get("rtfds_feature_tier_rows_total",
                   tier="dense").value == 2 * n - 3
    # served all the same (the sketch's overestimate: 3 rows that day)
    np.testing.assert_array_equal(res.features[:3, 3], [3.0, 3.0, 3.0])
    assert res.features[3, 3] == 1.0
    np.testing.assert_array_equal(res.customer_id[:4], [-1, -1, -1, -2])
    # and the directory holds no entry for it
    kd = eng.state.feature_state.customer_dir
    live = np.asarray(kd.slots) >= 0
    stored = join_key(np.stack([np.asarray(kd.keys_lo)[live],
                                np.asarray(kd.keys_hi)[live]]))
    assert int(live.sum()) == n - 3 and np.uint64(0xFFFFFFFFFFFFFFFF) \
        not in stored
