"""Sharded tiered exact feature store (key_mode="exact" on the mesh):
bit-identity vs single-engine exact and direct mode, AOT≡jit, overflow
tier accounting per shard, per-shard compaction, directory-routed
feedback, checkpoint/restore + elastic reshard, and the pinned error
messages for the combos that stay unsupported.

Bit-identity protocol: the streams below use WHOLE-DOLLAR amounts
(integer-valued f32), so every window amount-sum is exact in f32 and
therefore independent of accumulation order — the one arithmetic
degree of freedom the owner exchange has (it permutes rows, which
reorders f32 adds; with integer-valued amounts the sums are exact, so
the comparison isolates the STATE plane: placement, admission,
tiering, exchange, compaction). With fractional amounts the sharded
engine's documented contract is the existing 1e-6 tolerance
(test_sharded_engine.py), unchanged by this feature.
"""

import dataclasses as dc

import numpy as np
import pytest

from real_time_fraud_detection_system_tpu.config import (
    Config,
    FeatureConfig,
    RuntimeConfig,
)
from real_time_fraud_detection_system_tpu.io.checkpoint import Checkpointer
from real_time_fraud_detection_system_tpu.models.logreg import init_logreg
from real_time_fraud_detection_system_tpu.models.scaler import Scaler
from real_time_fraud_detection_system_tpu.runtime.engine import (
    ScoringEngine,
)
from real_time_fraud_detection_system_tpu.runtime.sharded_engine import (
    ShardedScoringEngine,
)
from real_time_fraud_detection_system_tpu.utils.metrics import (
    MetricsRegistry,
    MetricsServer,
)

DAY0 = 20200
N_DEV = 4


def _cfg(key_mode="exact", cust_cap=512, term_cap=512, rows=256, **feat_kw):
    return Config(
        features=FeatureConfig(
            key_mode=key_mode, customer_capacity=cust_cap,
            terminal_capacity=term_cap, cms_width=1 << 10, **feat_kw),
        runtime=RuntimeConfig(batch_buckets=(rows,), max_batch_rows=rows,
                              trigger_seconds=0.0),
    )


def _model():
    return init_logreg(15), Scaler(mean=np.zeros(15, np.float32),
                                   scale=np.ones(15, np.float32))


def _cols(rng, n=256, tx0=0, day=DAY0, n_cust=100, n_term=200):
    """Whole-dollar amounts: integer-valued f32 → order-independent
    window sums → the sharded/single comparison can be BIT-exact."""
    return {
        "tx_id": np.arange(tx0, tx0 + n, dtype=np.int64),
        "tx_datetime_us": (day * 86400
                           + rng.integers(0, 86400, n)).astype(np.int64)
        * 1_000_000,
        "customer_id": rng.integers(0, n_cust, n).astype(np.int64),
        "terminal_id": rng.integers(0, n_term, n).astype(np.int64),
        "tx_amount_cents": (rng.integers(1, 500, n) * 100).astype(
            np.int64),
        "kafka_ts_ms": np.zeros(n, dtype=np.int64),
    }


class _Src:
    def __init__(self, batches):
        self._b = list(batches)
        self._i = 0

    def poll_batch(self):
        if self._i >= len(self._b):
            return None
        b = self._b[self._i]
        self._i += 1
        return b

    @property
    def offsets(self):
        return [self._i]

    def seek(self, offsets):
        self._i = int(offsets[0])


def _batches(n_batches, rows=256, seed=3, day_step=1, n_cust=100,
             n_term=200):
    rng = np.random.default_rng(seed)
    return [
        _cols(rng, n=rows, tx0=i * rows, day=DAY0 + i * day_step,
              n_cust=n_cust, n_term=n_term)
        for i in range(n_batches)
    ]


# ---------------------------------------------------------------------------
# bit-identity: sharded exact ≡ single exact ≡ direct
# ---------------------------------------------------------------------------

def test_sharded_exact_bit_identical_to_single_and_direct():
    """With every shard's hot tier sized to hold its keys, the sharded
    exact engine must serve BIT-identically to the single-chip exact
    engine, and hence to direct mode — engine level, multi-batch."""
    params, scaler = _model()
    outs = {}
    for name, build in (
        ("direct", lambda: ScoringEngine(_cfg("direct"), "logreg",
                                         params, scaler)),
        ("exact1", lambda: ScoringEngine(_cfg(), "logreg", params,
                                         scaler)),
        ("exactN", lambda: ShardedScoringEngine(
            _cfg(), "logreg", params, scaler, n_devices=N_DEV)),
    ):
        eng = build()
        res = [eng.process_batch(b) for b in _batches(4)]
        outs[name] = (
            np.concatenate([r.probs for r in res]),
            np.concatenate([r.features for r in res]),
        )
    for other in ("exact1", "exactN"):
        np.testing.assert_array_equal(outs["direct"][0], outs[other][0],
                                      err_msg=f"probs {other}")
        np.testing.assert_array_equal(outs["direct"][1], outs[other][1],
                                      err_msg=f"features {other}")


def test_sharded_exact_jit_and_eager_levels_match_single():
    """Step level, below the engine: the sharded jit step's outputs on
    an owner-partitioned chunk equal the single-chip exact jit step's
    on the same rows (jit level) — and at the EAGER level
    (jax.disable_jit, where shard_map has no serving mode and jit-vs-
    eager classifier ULPs make cross-mode compares meaningless) the
    tiering itself is proven: single-chip exact ≡ direct bit-exactly
    with jit disabled end-to-end."""
    import jax
    import jax.numpy as jnp

    from real_time_fraud_detection_system_tpu.core.batch import (
        make_batch,
        pack_batch,
    )
    from real_time_fraud_detection_system_tpu.parallel.step import (
        partition_batch_by_customer,
    )

    params, scaler = _model()
    cfg = _cfg(rows=128)
    rng = np.random.default_rng(5)
    cols = _cols(rng, n=128)

    def run_single(mode="exact"):
        eng = ScoringEngine(_cfg(mode, rows=128) if mode != "exact"
                            else cfg, "logreg", params, scaler)
        r = eng.process_batch({k: v.copy() for k, v in cols.items()})
        return r.probs, r.features

    def run_sharded():
        eng = ShardedScoringEngine(cfg, "logreg", params, scaler,
                                   n_devices=N_DEV, rows_per_shard=64)
        part, pos = partition_batch_by_customer(
            {k: v.copy() for k, v in cols.items()}, N_DEV, 64)
        batch = make_batch(
            customer_id=part["customer_id"],
            terminal_id=part["terminal_id"],
            tx_datetime_us=part["tx_datetime_us"],
            amount_cents=part["tx_amount_cents"],
        )._replace(valid=part["__valid__"])
        step = eng._ensure_step(False)
        out = step(eng.state.feature_state, eng.state.params,
                   eng.state.scaler, jnp.asarray(pack_batch(batch)))
        # the engine's step also reports its exchange (last): nothing on
        # the full branch, the tight bucket's lanes on all devices (2 x the
        # 128 rows' 32 a device over 4 owners), every row travelled
        fstate, p, probs, feats, tier = out[:5]
        assert out[5].tolist() == [0, N_DEV * N_DEV * 16, 128]
        return np.asarray(probs)[pos], np.asarray(feats)[pos]

    p1, f1 = run_single()
    p2, f2 = run_sharded()
    np.testing.assert_array_equal(p1, p2)
    np.testing.assert_array_equal(f1, f2)

    # eager level: the tiered store ≡ direct placement with jit
    # disabled end-to-end (full-capacity tier, so every key admits)
    with jax.disable_jit():
        pe, fe = run_single("exact")
        pd, fd = run_single("direct")
    np.testing.assert_array_equal(pe, pd)
    np.testing.assert_array_equal(fe, fd)


def test_sharded_exact_aot_equals_jit_zero_recompiles():
    """AOT≡jit on the mesh: a precompiled sharded exact run (all three
    inventory variants, compaction firing) serves bit-identically to
    the plain-jit engine with zero counted recompiles/fallbacks."""
    params, scaler = _model()
    cfg = _cfg(compact_every=2)
    pre = cfg.replace(runtime=dc.replace(cfg.runtime, precompile=True))

    reg = MetricsRegistry()
    eng = ShardedScoringEngine(pre, "logreg", params, scaler,
                               n_devices=N_DEV, metrics=reg)
    keys = [s.key for s in eng.dispatch_inventory()]
    assert sorted(keys, key=str) == sorted(
        [("sharded", False), ("sharded", True), ("compact",)], key=str)
    man = eng.precompile()
    assert man["variants"] == 3
    res_aot = [eng.process_batch(b) for b in _batches(6, day_step=10)]
    rc = reg.get("rtfds_xla_recompiles_total")
    assert rc is None or rc.value == 0
    assert reg.get("rtfds_aot_fallbacks_total").value == 0
    assert reg.get("rtfds_precompiled_steps_total").value == 3

    ref = ShardedScoringEngine(cfg, "logreg", params, scaler,
                               n_devices=N_DEV)
    res_jit = [ref.process_batch(b) for b in _batches(6, day_step=10)]
    for a, b in zip(res_aot, res_jit):
        np.testing.assert_array_equal(a.probs, b.probs)
        np.testing.assert_array_equal(a.features, b.features)


def test_sharded_exact_routed_spill_matches_single_chip():
    """ONE hot customer (every row on one owner): the dense-spill ROUTED
    variant carries exact-mode admission over ICI and still reproduces
    the single-chip exact scores bit-exactly (chunk-aligned single-chip
    batches, whole-dollar stream)."""
    params, scaler = _model()
    n, rps = 128, 16
    total = N_DEV * rps  # routed-chunk width: 64 rows per spill chunk
    cfg = Config(
        features=FeatureConfig(key_mode="exact", customer_capacity=512,
                               terminal_capacity=512, cms_width=1 << 10),
        runtime=RuntimeConfig(batch_buckets=(rps, total),
                              max_batch_rows=n, trigger_seconds=0.0))
    rng = np.random.default_rng(11)
    cols = _cols(rng, n=n, n_term=13)
    cols["customer_id"] = np.full(n, 3, dtype=np.int64)  # ONE hot key

    # single-chip reference batched exactly like the sharded chunks:
    # owner-local chunk of rps rows, then dense routed chunks of
    # n_dev × rps rows each (in-batch visibility is chunk-granular)
    single = ScoringEngine(cfg, "logreg", params, scaler)
    bounds = [0, rps] + list(range(rps + total, n + 1, total))
    if bounds[-1] != n:
        bounds.append(n)
    refs = [
        single.process_batch(
            {k: v[a:b] for k, v in cols.items()})
        for a, b in zip(bounds, bounds[1:])
    ]

    eng = ShardedScoringEngine(cfg, "logreg", params, scaler,
                               n_devices=N_DEV, rows_per_shard=rps)
    res = eng.process_batch(cols)
    assert eng._sharded_step_routed is not None  # spill path exercised
    np.testing.assert_array_equal(
        res.probs, np.concatenate([r.probs for r in refs]))
    np.testing.assert_array_equal(
        res.features, np.concatenate([r.features for r in refs]))


# ---------------------------------------------------------------------------
# overflow tier + per-shard telemetry
# ---------------------------------------------------------------------------

def test_sharded_exact_overflow_counts_per_shard_and_healthz():
    """A 100×-oversubscribed hot tier overflows to each shard's sketch
    replica: dense + cms == rows × keyspaces exactly, the shard-labeled
    counters sum to the table-level ones, and /healthz carries the
    per-shard breakdown with the worst shard named."""
    params, scaler = _model()
    reg = MetricsRegistry()
    rows, n_b = 256, 4
    eng = ShardedScoringEngine(
        _cfg(cust_cap=64, term_cap=64, rows=rows), "logreg", params,
        scaler, n_devices=N_DEV, metrics=reg)
    stats = eng.run(_Src(_batches(n_b, rows=rows, n_cust=5000,
                                  n_term=5000)))
    assert stats["rows"] == rows * n_b
    dense = reg.get("rtfds_feature_tier_rows_total", tier="dense").value
    cms = reg.get("rtfds_feature_tier_rows_total", tier="cms").value
    assert dense + cms == rows * n_b * 2
    assert cms > 0, "64-slot tier under 5000 keys must overflow"
    assert dense > 0
    for tier, total in (("dense", dense), ("cms", cms)):
        shard_vals = [
            reg.get("rtfds_feature_tier_rows_total", tier=tier,
                    shard=str(s)).value
            for s in range(N_DEV)
        ]
        assert sum(shard_vals) == total, tier
    # healthz per-shard breakdown requires an occupancy read, which
    # lands at compaction cadence — force one metering pass
    eng._record_compaction(eng.state.feature_state,
                           np.zeros((N_DEV, 2), np.int32))
    _, body = MetricsServer(registry=reg).health()
    fs = body["feature_state"]
    assert set(fs["slots_occupied_per_shard"]) == {
        str(s) for s in range(N_DEV)}
    assert fs["worst_shard"]["occupied"] == max(
        fs["slots_occupied_per_shard"].values())
    assert fs["tier_rows"]["dense"] == dense  # global view unchanged
    assert fs["tier_rows_per_shard"]["0"]["dense"] >= 0


def test_sharded_exact_misses_are_read_in_chunks_like_the_whole_batch(
        monkeypatch):
    """Behind the exchange the plane reads the sketch only for the rows
    that missed admission on their owner, a chunk at a time
    (``ops/cms.cms_query_where``); what every delivered row gets is what
    the whole-batch read, selected per row, gave it — the read as it
    stood before PR 33, put in the loop's place for the comparison."""
    from real_time_fraud_detection_system_tpu.features import online
    from real_time_fraud_detection_system_tpu.ops import cms

    def whole_batch(sk, columns, key, day, rows, windows, delay=0):
        return cms._cms_query_tables(
            sk, tuple(getattr(sk, c) for c in columns), key, day, windows,
            delay), 0

    params, scaler = _model()
    rows, n_b = 256, 3
    outs, tiers = [], []
    for stand_in in (None, whole_batch):
        if stand_in is not None:
            monkeypatch.setattr(online, "cms_query_where", stand_in)
        reg = MetricsRegistry()
        eng = ShardedScoringEngine(
            _cfg(cust_cap=64, term_cap=64, rows=rows), "logreg", params,
            scaler, n_devices=N_DEV, metrics=reg)
        res = [eng.process_batch(b) for b in _batches(
            n_b, rows=rows, n_cust=5000, n_term=5000)]
        outs.append((np.concatenate([r.probs for r in res]),
                     np.concatenate([r.features for r in res])))
        tiers.append([reg.get("rtfds_feature_tier_rows_total",
                              tier=t).value for t in ("dense", "cms")])
    assert tiers[0] == tiers[1] and tiers[0][1] > rows  # most rows missed
    np.testing.assert_array_equal(outs[0][0], outs[1][0])
    np.testing.assert_array_equal(outs[0][1], outs[1][1])
    assert np.isfinite(outs[0][1]).all()


def test_sharded_exact_compaction_reclaims_on_every_shard():
    """A DRIFTING working set (disjoint key range per batch) with the
    day marching 10/batch past the 37-day horizon: the per-shard
    compaction pass reclaims on EVERY shard (consecutive ids spread
    over all residues), metered by the shard-labeled reclaim
    counters."""
    params, scaler = _model()
    reg = MetricsRegistry()
    eng = ShardedScoringEngine(
        _cfg(compact_every=3), "logreg", params, scaler,
        n_devices=N_DEV, metrics=reg)
    rng = np.random.default_rng(3)
    batches = []
    for i in range(9):
        c = _cols(rng, n=256, tx0=i * 256, day=DAY0 + i * 10)
        # working set drifts: batch i touches keys [i*64, i*64+64) only,
        # so earlier batches' slots go provably dead past the horizon
        c["customer_id"] = (i * 64
                            + rng.integers(0, 64, 256)).astype(np.int64)
        c["terminal_id"] = (i * 64
                            + rng.integers(0, 64, 256)).astype(np.int64)
        batches.append(c)
    eng.run(_Src(batches))
    for s in range(N_DEV):
        rec = reg.get("rtfds_feature_slots_reclaimed_total",
                      table="terminal", shard=str(s))
        assert rec is not None and rec.value > 0, f"shard {s}"
        occ = reg.get("rtfds_feature_slots_occupied", table="terminal",
                      shard=str(s))
        assert occ is not None and 0 <= occ.value <= 512 // N_DEV
    # table-level totals are the shard sums (no double counting)
    total = reg.get("rtfds_feature_slots_reclaimed_total",
                    table="terminal").value
    assert total == sum(
        reg.get("rtfds_feature_slots_reclaimed_total", table="terminal",
                shard=str(s)).value for s in range(N_DEV))


@pytest.mark.parametrize("cold", [False, True],
                         ids=["dead-only", "cold-tier-demotes"])
def test_each_devices_pass_equals_the_entry_wide_oracle(cold, tmp_path):
    """The pass inside ``shard_map``: every device runs it on its own
    directory and its own block of the window columns — no collective in
    its loops, each device's trips follow its own counts — and leaves,
    device by device, the entry-wide oracle's directory, free stack,
    columns, counts and demote payload to the bit
    (``tests/test_exact_store.py`` keeps the oracle). The sweep counter
    is the mesh's sum: one a device and table that gave something up."""
    from test_exact_store import (
        _drifting,
        assert_recorded_pass_equals_oracle,
        assert_sweeps_counted,
        spy_on_passes,
    )

    params, scaler = _model()
    reg = MetricsRegistry()
    if cold:
        # test_cold_exact.py's sizing: keys of a universe four times the
        # hot tier, a new day and a pass every batch, 16 probes
        from test_cold_exact import _churn

        cfg = _cfg(cust_cap=256, term_cap=256, rows=64, keydir_probes=16,
                   compact_every=1, cold_store=str(tmp_path / "cold"),
                   cold_demote_slots=64, cold_highwater=0.5)
        batches, demote = _churn(7, 12, 64, 1024), 64
    else:
        cfg = _cfg(compact_every=3)
        batches, demote = _drifting(12), 0
    cfg = dc.replace(cfg, runtime=dc.replace(cfg.runtime,
                                             precompile=False))
    eng = ShardedScoringEngine(cfg, "logreg", params, scaler,
                               n_devices=N_DEV, metrics=reg)
    seen = spy_on_passes(eng)
    eng.run(_Src(batches))
    assert len(seen) == (12 if cold else 4)
    swept = {"customer": 0, "terminal": 0}
    for before, day, out in seen:
        for t, n in assert_recorded_pass_equals_oracle(
                before, day, out, cfg.features, demote,
                n_dev=N_DEV).items():
            swept[t] += n
    # devices that gave nothing up in a pass, and devices that did
    assert 0 < swept["customer"] < len(seen) * N_DEV
    assert_sweeps_counted(reg, len(seen), swept)


# ---------------------------------------------------------------------------
# feedback: directory-routed labels
# ---------------------------------------------------------------------------

def test_sharded_exact_feedback_routes_hits_dense_misses_to_sketch():
    from real_time_fraud_detection_system_tpu.features.spec import (
        FEATURE_NAMES,
    )

    params, scaler = _model()
    cfg = _cfg(rows=64)
    eng = ShardedScoringEngine(cfg, "logreg", params, scaler,
                               n_devices=N_DEV)
    delay = cfg.features.delay_days
    n = 8
    rng = np.random.default_rng(2)

    def cols_for(day, tx0):
        c = _cols(rng, n=n, tx0=tx0, day=day)
        c["terminal_id"] = np.full(n, 7, dtype=np.int64)
        return c

    eng.process_batch(cols_for(DAY0, 0))
    # HIT: terminal 7 was admitted by the batch above — the label lands
    # in the owner's dense window row and raises delay-shifted risk
    eng.apply_state_feedback(np.full(n, 7, np.int64),
                             np.full(n, DAY0, np.int32),
                             np.ones(n, np.int32))
    res = eng.process_batch(cols_for(DAY0 + delay + 1, 100))
    risk_cols = [i for i, nm in enumerate(FEATURE_NAMES) if "RISK" in nm]
    assert res.features[:, risk_cols].max() > 0
    assert res.features[:, risk_cols].max() <= 1.0 + 1e-6

    # MISS: a terminal never admitted routes to its owner shard's
    # sketch replica's fraud column (no dense slot is ever inserted)
    sk0 = np.asarray(eng.state.feature_state.terminal_cms.fraud).sum()
    eng.apply_state_feedback(np.full(2, 424242, np.int64),
                             np.full(2, DAY0, np.int32),
                             np.ones(2, np.int32))
    sk1 = np.asarray(eng.state.feature_state.terminal_cms.fraud).sum()
    # the original day's sketch slice may have rotated; the miss only
    # lands while the slice still holds DAY0 — assert no dense insert
    # happened either way, and the sketch never lost mass
    assert sk1 >= sk0
    from real_time_fraud_detection_system_tpu.core.batch import fold_key
    from real_time_fraud_detection_system_tpu.ops.keydir import (
        lookup_slots_stacked,
    )
    import jax.numpy as jnp

    key = fold_key(np.asarray([424242])).astype(np.uint32)
    owner = (key % np.uint32(N_DEV)).astype(np.int32)
    _, hit = lookup_slots_stacked(
        eng.state.feature_state.terminal_dir, jnp.asarray(owner),
        jnp.asarray(key), jnp.ones(1, bool))
    assert not bool(np.asarray(hit)[0]), \
        "feedback must never insert into the directory"


# ---------------------------------------------------------------------------
# durable state: checkpoint/restore + elastic reshard
# ---------------------------------------------------------------------------

def test_sharded_exact_checkpoint_restore_bit_identical(tmp_path):
    """Crash-resume at the SAME width: restore re-places the per-shard
    directories and the continuation is bit-identical to an
    uninterrupted run."""
    params, scaler = _model()
    cfg = _cfg()
    batches = _batches(5)

    clean = ShardedScoringEngine(cfg, "logreg", params, scaler,
                                 n_devices=N_DEV)
    ref = [clean.process_batch(b) for b in batches]

    ck = Checkpointer(str(tmp_path / "ck"))
    eng = ShardedScoringEngine(cfg, "logreg", params, scaler,
                               n_devices=N_DEV)
    for b in batches[:2]:
        eng.process_batch(b)
    ck.save(eng.state)

    eng2 = ShardedScoringEngine(cfg, "logreg", params, scaler,
                                n_devices=N_DEV)
    assert ck.restore(eng2.state) is not None
    out = [eng2.process_batch(b) for b in batches[2:]]
    for a, b in zip(ref[2:], out):
        np.testing.assert_array_equal(a.probs, b.probs)
        np.testing.assert_array_equal(a.features, b.features)


def test_sharded_exact_elastic_restore_2_to_4_and_back_to_1(tmp_path):
    """Elastic N→M through the checkpoint plane: a 2-shard exact
    checkpoint restores into a 4-shard engine (directory entries
    re-homed, layout recorded) and into a single-chip exact engine —
    both continuations bit-identical to the uninterrupted 2-shard
    run."""
    params, scaler = _model()
    cfg = _cfg()
    batches = _batches(4)
    tail = _batches(2, seed=23, day_step=1)

    e2 = ShardedScoringEngine(cfg, "logreg", params, scaler, n_devices=2)
    for b in batches:
        e2.process_batch(b)
    ck = Checkpointer(str(tmp_path / "ck"))
    ck.save(e2.state)
    ref = [e2.process_batch(b) for b in tail]

    e4 = ShardedScoringEngine(cfg, "logreg", params, scaler, n_devices=4)
    restored = ck.restore(e4.state)
    assert restored is not None and restored.layout_devices == 2
    out4 = [e4.process_batch(b) for b in tail]
    assert e4.state.layout_devices == 4
    for a, b in zip(ref, out4):
        np.testing.assert_array_equal(a.probs, b.probs)
        np.testing.assert_array_equal(a.features, b.features)

    e1 = ScoringEngine(cfg, "logreg", params, scaler)
    assert ck.restore(e1.state) is not None
    out1 = [e1.process_batch(b) for b in tail]
    for a, b in zip(ref, out1):
        np.testing.assert_array_equal(a.probs, b.probs)
        np.testing.assert_array_equal(a.features, b.features)


def test_reshard_exact_roundtrip_preserves_admitted_state():
    """1→2→4→1: every admitted key's window row and the free-stack
    height survive the round trip exactly (slot ids may permute — the
    directory, not the slot id, is the contract)."""
    import jax
    import jax.numpy as jnp

    from real_time_fraud_detection_system_tpu.ops.keydir import (
        lookup_slots,
    )
    from real_time_fraud_detection_system_tpu.parallel.mesh import (
        reshard_feature_state,
    )

    params, scaler = _model()
    cfg = _cfg()
    eng = ScoringEngine(cfg, "logreg", params, scaler)
    for b in _batches(3):
        eng.process_batch(b)
    st = jax.tree.map(np.asarray, eng.state.feature_state)
    s1 = reshard_feature_state(
        reshard_feature_state(
            reshard_feature_state(st, cfg, 1, 2), cfg, 2, 4),
        cfg, 4, 1)

    from real_time_fraud_detection_system_tpu.core.batch import fold_key

    keys = jnp.asarray(fold_key(np.arange(200)).astype(np.uint32))
    valid = jnp.ones(200, bool)
    slot_a, hit_a = lookup_slots(st.terminal_dir, keys, valid)
    slot_b, hit_b = lookup_slots(s1.terminal_dir, keys, valid)
    np.testing.assert_array_equal(np.asarray(hit_a), np.asarray(hit_b))
    for leaf, ta, tb in zip(("bucket_day", "count", "amount", "fraud"),
                            st.terminal.tables(), s1.terminal.tables()):
        a = ta[np.asarray(slot_a)]
        b = tb[np.asarray(slot_b)]
        np.testing.assert_array_equal(
            a[np.asarray(hit_a)], b[np.asarray(hit_b)], err_msg=leaf)
    assert int(np.asarray(st.terminal_dir.free_top)) == int(
        np.asarray(s1.terminal_dir.free_top))


def test_reshard_exact_overloaded_shard_raises_loudly():
    """Shrinking cap_local below one residue class's live-key count
    cannot be represented — must raise with the fix named, never drop
    admitted state silently."""
    import jax

    params, scaler = _model()
    cfg = _cfg(cust_cap=8, term_cap=8, rows=64)
    eng = ScoringEngine(cfg, "logreg", params, scaler)
    rng = np.random.default_rng(1)
    c = _cols(rng, n=64)
    # five terminals in residue class 0 (mod 4): new shard 0 at n_new=4
    # would own 5 keys against cap_local = 2
    c["terminal_id"] = np.asarray([0, 4, 8, 12, 16] * 12 + [0] * 4,
                                  np.int64)
    eng.process_batch(c)

    from real_time_fraud_detection_system_tpu.parallel.mesh import (
        reshard_feature_state,
    )

    st = jax.tree.map(np.asarray, eng.state.feature_state)
    with pytest.raises(ValueError, match="compaction"):
        reshard_feature_state(st, cfg, 1, 4)


def test_cross_width_restore_capacity_mismatch_still_quarantines(
        tmp_path):
    """The cross-width shape relaxation is NARROW: only the
    width-dependent planes (directories, sketch replicas) may differ.
    A checkpoint written under a different terminal_capacity mismatches
    on the width-INDEPENDENT window tables too — that must stay an
    'incompatible' quarantine-and-fallback (restore returns None /
    falls back), never leak through to a hard reshard crash."""
    params, scaler = _model()
    writer = ShardedScoringEngine(
        _cfg(term_cap=256), "logreg", params, scaler, n_devices=2)
    for b in _batches(2):
        writer.process_batch(b)
    ck = Checkpointer(str(tmp_path / "ck"))
    ck.save(writer.state)

    from real_time_fraud_detection_system_tpu.utils.metrics import (
        get_registry,
    )

    reader = ShardedScoringEngine(
        _cfg(term_cap=512), "logreg", params, scaler, n_devices=4)
    corrupt0 = get_registry().family_total(
        "rtfds_checkpoint_corrupt_total") or 0
    assert ck.restore(reader.state) is None  # quarantined, no fallback
    assert (get_registry().family_total("rtfds_checkpoint_corrupt_total")
            or 0) > corrupt0


def test_ckpt_inspect_reports_per_shard_state(tmp_path):
    """`rtfds ckpt --inspect` surfaces per-shard directory occupancy and
    per-shard leaf bytes from the manifest alone — state skew without
    loading the checkpoint."""
    from real_time_fraud_detection_system_tpu.io.checkpoint import (
        feature_state_report,
    )

    params, scaler = _model()
    eng = ShardedScoringEngine(_cfg(), "logreg", params, scaler,
                               n_devices=N_DEV)
    for b in _batches(2):
        eng.process_batch(b)
    ck = Checkpointer(str(tmp_path / "ck"))
    ck.save(eng.state)

    man = ck.manifest(ck.latest())
    fs = feature_state_report(man)
    assert fs is not None
    assert fs["layout_devices"] == N_DEV
    occ = fs["occupancy_per_shard"]
    assert set(occ) == {"customer", "terminal"}
    assert len(occ["terminal"]) == N_DEV
    assert sum(occ["terminal"]) > 0
    assert fs["worst_shard"]["terminal"]["occupied"] == max(
        occ["terminal"])
    # named leaves: directory leaves carry per-shard byte attribution
    dir_leaves = [l for l in fs["leaves"]
                  if "terminal_dir" in l["path"]]
    assert dir_leaves and all(
        l["per_shard_bytes"] * N_DEV == l["bytes"] for l in dir_leaves)
    # and the CLI renders the block (subprocess-free: call the command)
    import io
    from contextlib import redirect_stdout

    from real_time_fraud_detection_system_tpu.cli import main as cli_main

    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = cli_main(["ckpt", "--path", str(tmp_path / "ck"),
                       "--inspect", ck.latest().split("/")[-1]])
    assert rc == 0
    assert '"feature_state"' in buf.getvalue()
    assert '"occupancy_per_shard"' in buf.getvalue()


# ---------------------------------------------------------------------------
# pinned error messages for the combos that stay unsupported
# ---------------------------------------------------------------------------

def test_sharded_exact_nan_guard_still_refused():
    """The engine-wide nan-guard refusal (no pre-batch anchor under
    donation inside shard_map) covers exact mode too — message
    pinned."""
    params, scaler = _model()
    cfg = _cfg()
    cfg = cfg.replace(runtime=dc.replace(cfg.runtime, nan_guard=True))
    with pytest.raises(ValueError, match="nan_guard"):
        ShardedScoringEngine(cfg, "logreg", params, scaler,
                             n_devices=N_DEV)


def test_sharded_exact_mislaid_state_refused_with_fix_named():
    """A provided exact state in a different shard layout is
    detectable (directory shapes carry the width) — refused with
    feature_state_n_old named, never served as split key histories."""
    from real_time_fraud_detection_system_tpu.features.online import (
        init_feature_state,
    )

    params, scaler = _model()
    cfg = _cfg()
    single = init_feature_state(cfg.features)  # single-chip layout
    with pytest.raises(ValueError, match="feature_state_n_old"):
        ShardedScoringEngine(cfg, "logreg", params, scaler,
                             n_devices=N_DEV, feature_state=single)


def test_sharded_exact_indivisible_capacity_refused():
    params, scaler = _model()
    cfg = _cfg(cust_cap=4, term_cap=512)  # pow2, but 4 / 8 devices
    with pytest.raises(ValueError, match="power of two"):
        ShardedScoringEngine(cfg, "logreg", params, scaler, n_devices=8)


# ---------------------------------------------------------------------------
# the claim rounds' counter (PR 35), one device and the mesh
# ---------------------------------------------------------------------------

def _rounds_needed(kd_before, kd_after, keys, n_probes):
    """Claim rounds a batch of ``keys`` needs, read off the directory
    the FIXED rounds left: a new key placed at its j-th probe position
    was unplaced through round j, so the slowest new key decides — none
    new, no round; a key in no position, all of them."""
    import jax.numpy as jnp

    from real_time_fraud_detection_system_tpu.ops.keydir import (
        _probe_positions,
    )

    keys = np.unique(keys)
    pos = np.asarray(_probe_positions(
        jnp.asarray(keys), kd_before.dir_capacity, n_probes))
    new = ~(np.asarray(kd_before.keys)[pos] == keys[:, None]).any(axis=1)
    at = np.asarray(kd_after.keys)[pos] == keys[:, None]
    placed_in = np.where(at.any(axis=1), at.argmax(axis=1) + 1, n_probes)
    return int(placed_in[new].max(initial=0))


def _engine_on(cfg, n_dev, reg):
    """The mesh's engine over ``n_dev`` devices, or one chip's at 0."""
    params, scaler = _model()
    if n_dev:
        return ShardedScoringEngine(cfg, "logreg", params, scaler,
                                    n_devices=n_dev, metrics=reg)
    return ScoringEngine(cfg, "logreg", params, scaler, metrics=reg)


@pytest.mark.parametrize("n_dev", [0, N_DEV], ids=["one-device", "mesh"])
def test_claim_rounds_counter_is_what_the_fixed_rounds_need(n_dev):
    """``rtfds_keydir_claim_rounds_total{table=…}`` after a run over
    known keys is the sum, batch by batch and shard by shard, of the
    rounds the fixed-depth reference (``tests/test_keydir.py``'s
    unrolled ``admit_slots``) needed to place that shard's new keys —
    under 2 x 16 a batch, and NOTHING for a pass over keys the
    directories already hold."""
    import jax
    import jax.numpy as jnp
    from test_keydir import _admit_slots_unrolled

    from real_time_fraud_detection_system_tpu.core.batch import fold_key
    from real_time_fraud_detection_system_tpu.ops.keydir import init_keydir

    cfg, reg, rows = _cfg(), MetricsRegistry(), 256
    n, probes = max(1, n_dev), cfg.features.keydir_probes
    eng = _engine_on(cfg, n_dev, reg)
    fixed = jax.jit(_admit_slots_unrolled, static_argnames="n_probes")
    cap = cfg.features.customer_capacity // n  # == terminal_capacity
    dirs = {t: [init_keydir(2 * cap, cap) for _ in range(n)]
            for t in ("customer", "terminal")}
    want = dict.fromkeys(dirs, 0)
    want_shard = {(t, s): 0 for t in dirs for s in range(n)}
    batches = _batches(4, rows=rows)
    for b in batches:
        eng.process_batch(b)
        for table in dirs:
            keys = fold_key(b[f"{table}_id"])
            for s in range(n):
                own = keys[keys % np.uint32(n) == s]
                padded = np.zeros(rows, np.uint32)
                padded[:own.size] = own
                kd = dirs[table][s]
                dirs[table][s] = fixed(
                    kd, jnp.asarray(padded),
                    jnp.arange(rows) < own.size, n_probes=probes)[0]
                ran = _rounds_needed(kd, dirs[table][s], own, probes)
                want[table] += ran
                want_shard[(table, s)] += ran
    got = {t: reg.get("rtfds_keydir_claim_rounds_total", table=t).value
           for t in dirs}
    assert got == want and all(0 < v < probes * n * len(batches)
                               for v in got.values()), (got, want)
    # the mesh keeps each shard's rounds too, under a name of its own:
    # the benchmark's rounds ÷ batches sums every series of a name
    by_shard = "rtfds_keydir_shard_claim_rounds_total"
    if n_dev:
        assert want_shard == {
            (t, s): reg.get(by_shard, table=t, shard=str(s)).value
            for t, s in want_shard}
        assert reg.family_total(
            "rtfds_keydir_claim_rounds_total") == sum(want.values())
    else:
        assert reg.family_total(by_shard) is None
    for b in batches:  # every key known now: not one round more
        eng.process_batch(b)
    assert got == {
        t: reg.get("rtfds_keydir_claim_rounds_total", table=t).value
        for t in dirs}
    assert reg.get("rtfds_batches_total").value == 2 * len(batches)


@pytest.mark.parametrize("key_mode,n_dev", [
    ("direct", 0), ("hash", 0), ("direct", N_DEV)])
def test_no_claim_rounds_series_without_a_directory(key_mode, n_dev):
    """``direct`` / ``hash`` take the slot from ``key_slot``: no admit
    runs and the registry holds no claim-rounds series for a dashboard
    (or the benchmark's ``keydir_claim_rounds.sat``) to read as zero."""
    reg = MetricsRegistry()
    eng = _engine_on(_cfg(key_mode), n_dev, reg)
    eng.process_batch(_batches(1)[0])
    assert reg.get("rtfds_batches_total").value == 1
    assert reg.family_total("rtfds_keydir_claim_rounds_total") is None


def test_sharded_exact_claims_like_the_fixed_rounds_behind_the_exchange(
        monkeypatch):
    """Behind the exchange each owner's claim loop ends on its own rows;
    what every delivered row gets — and the directories the shards are
    left with — is what P fixed rounds gave, put in the loop's place for
    the comparison (``tests/test_keydir.py``'s unrolled ``admit_slots``),
    through an overflowing hot tier (a dry free stack's rolled-back
    claims) as well as an ample one."""
    import jax.numpy as jnp
    from test_keydir import _admit_slots_unrolled

    from real_time_fraud_detection_system_tpu.features import online

    def fixed_rounds(kd, key, valid, n_probes):
        return _admit_slots_unrolled(kd, key, valid, n_probes) + (
            jnp.int32(n_probes), None)

    params, scaler = _model()
    for caps in (dict(), dict(cust_cap=64, term_cap=64)):
        outs = []
        for stand_in in (None, fixed_rounds):
            with monkeypatch.context() as m:
                if stand_in is not None:
                    m.setattr(online, "admit_slots", stand_in)
                eng = ShardedScoringEngine(
                    _cfg(**caps), "logreg", params, scaler,
                    n_devices=N_DEV, metrics=MetricsRegistry())
                res = [eng.process_batch(b) for b in _batches(
                    3, n_cust=100 if not caps else 5000,
                    n_term=200 if not caps else 5000)]
            fs = eng.state.feature_state
            outs.append([np.concatenate([r.probs for r in res]),
                         np.concatenate([r.features for r in res])] + [
                np.asarray(leaf) for kd in (fs.customer_dir,
                                            fs.terminal_dir)
                for leaf in kd])
        for a, b in zip(*outs):
            assert a.tobytes() == b.tobytes()


# -- the benchmark's four-chip exact cell, through the harness's own path ----

# forest-rf100-d8-x4-exact at toy size: 40 fill days of 256 rows, 4,096 +
# 8,192 active keys in a universe of 16,384 + 32,768 ids over 8,192 +
# 16,384 slots (every shard's directory at a load under 0.25); keys arrive
# in every batch; the first compaction of the window comes after batch 42
X4_EXACT_TOY = {
    "config": {
        "features": {"customer_capacity": 8192, "terminal_capacity": 16384,
                     "compact_every": 42},
        "key_universe": {"customers": 16384, "terminals": 32768},
        "active_keys": {"customers": 4096, "terminals": 8192},
        "runtime": {"precompile": True, "batch_buckets": [256],
                    "max_batch_rows": 256},
        "model_params": {"fit_rows": 512, "nominal_rows_per_day": 256},
    },
    "traffic": {
        "fill_batches": 40, "fill_batch_rows": 256, "pool_envelopes": 2048,
        "draw_rows": 65536, "max_poll_rows": 256,
        "check_window_rows": 1 << 20,
    },
}


@pytest.fixture()
def harness_on_cpu():
    """``benchmark.harness`` with what ``claim_device`` sets for a chip
    run (the persistent cache's directory and floors) put back after."""
    import jax

    from benchmark import harness

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    was = {n: getattr(jax.config, n) for n in names}
    yield harness
    for n, v in was.items():
        jax.config.update(n, v)


@pytest.mark.parametrize("key_mode", ["exact", "direct"])
def test_x4_exact_cell_through_the_harness_and_the_plain_reference(
        harness_on_cpu, key_mode):
    """``forest-x4-exact.saturate`` as the benchmark runs it —
    ``harness.build_engine`` from the cell's configuration on a four-device
    mesh, ``precompile()``, the active-set generator, the Parquet sink, a
    compaction inside the window — with every row of every batch held to
    ``benchmark/reference.py`` under the configuration's limits. The same
    ids under ``key_mode=direct`` are the case the deployment exists to
    prevent: ids past the slot count merge, and the comparison says so."""
    import time

    harness = harness_on_cpu
    seen = {}
    over = X4_EXACT_TOY if key_mode == "exact" else harness.merge(
        X4_EXACT_TOY, {"config": {"features": {"key_mode": "direct"}}})
    result = harness.run_cell(
        "forest-x4-exact.saturate", 4_400_000_123, 1.5, False,
        time.perf_counter(), allow_cpu=True, overrides=over,
        sabotage=lambda engine, sink: seen.update(engine=engine))
    eng = seen["engine"]
    assert isinstance(eng, ShardedScoringEngine) and eng.n_dev == N_DEV
    assert result["device"]["count"] == N_DEV and result["failed"] == 0
    checks = {c["name"]: c for c in result["checks"]}
    assert checks["rows_compared"]["value"] >= result["attempted"] > 0
    assert checks["recompiles_in_window"]["value"] == 0
    if key_mode == "direct":
        assert result["correct"] is False
        assert checks["exact_columns_wrong"]["value"] > 0
        return
    assert result["correct"] is True, result["checks"]
    # what the cell's registry metrics read on a mesh, whose engine keeps
    # tier, occupancy and reclaim series twice (table-level, then by shard)
    snap = eng.metrics.snapshot()
    ctx = {"registry_before": {}, "registry_after": snap}

    cell = harness.Cell(harness.ROOT, harness.load_manifest(),
                        "forest-x4-exact.saturate")

    def metric(name):  # a metric file through its reader, as a traced run
        spec = harness.load_json(harness.find(
            cell.root, cell.manifest, "metrics", name, ".json"))
        return cell.plugin("readers", spec["reader"]).read(
            ctx, **spec["args"])

    def series(name, **labels):
        return [r for r in snap[name]["series"] if all(
            r["labels"].get(k) == v for k, v in labels.items())]

    shards = [str(s) for s in range(N_DEV)]
    passes = eng.metrics.get("rtfds_state_compactions_total").value
    assert metric("compactions.sat") == passes >= 1
    # `registry` takes the FIRST series that carries the file's labels:
    # the table-level one, registered before any shard's — the mesh-wide
    # count, equal to its shards' sum
    tiers = series("rtfds_feature_tier_rows_total", tier="cms")
    assert "shard" not in tiers[0]["labels"] and len(tiers) == 1 + N_DEV
    assert metric("tier_cms_rows.sat") == tiers[0]["value"] == sum(
        r["value"] for r in tiers[1:]) == 0.0
    dense = series("rtfds_feature_tier_rows_total", tier="dense")
    assert dense[0]["value"] == sum(r["value"] for r in dense[1:]) > 0
    # `registry_ratio` sums EVERY series of a name, so the accepted
    # slots_reclaimed.sat would count each slot twice here: the cell
    # reports slots_reclaimed_mesh.sat, the shard series alone
    rec = series("rtfds_feature_slots_reclaimed_total")
    by_table = sum(r["value"] for r in rec if "shard" not in r["labels"])
    assert by_table > 0
    assert metric("slots_reclaimed_mesh.sat") == by_table / passes
    assert metric("slots_reclaimed.sat") == 2 * by_table / passes
    # rounds a batch: the table-level series alone carry that name
    rounds = eng.metrics.family_total("rtfds_keydir_claim_rounds_total")
    assert metric("keydir_claim_rounds.sat") == rounds / eng.metrics.get(
        "rtfds_batches_total").value
    per_shard = [sum(r["value"] for r in series(
        "rtfds_keydir_shard_claim_rounds_total", shard=s)) for s in shards]
    assert sum(per_shard) == rounds
    assert metric("keydir_claim_rounds_spread.sat") == pytest.approx(
        max(per_shard) / (rounds / N_DEV))
    assert 1.0 <= metric("keydir_claim_rounds_spread.sat") < 1.5
    occ = {(r["labels"]["table"], r["labels"]["shard"]): r["value"]
           for r in series("rtfds_feature_slots_occupied")
           if "shard" in r["labels"]}
    caps = {"customer": 8192 // N_DEV, "terminal": 16384 // N_DEV}
    assert metric("keydir_occupancy_max.sat") == pytest.approx(
        max(v / caps[t] for (t, _), v in occ.items()))
    assert 0.0 < metric("keydir_occupancy_max.sat") < 0.5
