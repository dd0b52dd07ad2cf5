"""The traffic generator: seeded, real envelopes, open-loop polls."""

import os

import numpy as np
import pytest

from benchmark import harness
from benchmark.generators import debezium_cards as gen

ROOT = harness.ROOT


def _files():
    over = harness.load_json(os.path.join(
        ROOT, "benchmark", "tests", "data", "toy_overrides.json"))
    config = harness.merge(harness.load_json(os.path.join(
        ROOT, "benchmark", "configs", "forest-rf100-d8.json")),
        over["config"])
    traffic = harness.merge(harness.load_json(os.path.join(
        ROOT, "benchmark", "traffic", "steady-0.8.json")), over["traffic"])
    return config, traffic


def _decode(msgs, ts):
    from real_time_fraud_detection_system_tpu.core.envelope import (
        decode_transaction_envelopes,
    )

    return decode_transaction_envelopes(msgs, ts)


def test_same_seed_same_schedule_other_seed_another():
    config, traffic = _files()
    a = gen.build(traffic, config, 5_000_000_007, 2.0, _decode)
    b = gen.build(traffic, config, 5_000_000_007, 2.0, _decode)
    c = gen.build(traffic, config, 5_000_000_008, 2.0, _decode)
    for k in ("fill_customer", "fill_terminal", "fill_cents", "fill_us",
              "win_customer", "win_terminal", "win_cents", "due_s"):
        assert np.array_equal(getattr(a, k), getattr(b, k)), k
        assert not np.array_equal(getattr(a, k), getattr(c, k)), k
    # the same sizes and (nearly) the same number of arrivals for any seed
    assert len(a.fill_us) == len(c.fill_us)
    assert abs(len(a.due_s) - len(c.due_s)) < 0.05 * len(a.due_s)
    assert a.fill_customer.max() < config["key_universe"]["customers"]
    assert a.win_terminal.max() < config["key_universe"]["terminals"]


def test_envelopes_are_the_programs_wire_format():
    """The program's own decoder reads the benchmark's envelopes back to the
    values encoded, and they have the program's encoder's width."""
    from real_time_fraud_detection_system_tpu.core.envelope import (
        encode_transaction_envelopes,
    )

    rng = np.random.default_rng(3)
    n = 200
    cols = (np.arange(n, dtype=np.int64),
            1_749_636_000_000_000 + rng.integers(0, 10**9, n),
            rng.integers(0, 1 << 22, n), rng.integers(0, 1 << 23, n),
            rng.integers(1, 10**6, n))
    mine = gen.encode_envelopes(*cols)
    assert mine == encode_transaction_envelopes(*cols)
    got, invalid = _decode(mine, (cols[1] // 1000).tolist())
    assert not invalid.any()
    for k, v in zip(("tx_id", "tx_datetime_us", "customer_id", "terminal_id",
                     "tx_amount_cents"), cols):
        assert np.array_equal(got[k], v), k


def test_open_loop_poll_takes_what_is_due_and_serves_the_rest_after_close():
    config, traffic = _files()
    t = gen.build(traffic, config, 11, 0.05, _decode)
    fill = t.fill_source()
    first = fill.poll_batch()
    assert len(first["tx_id"]) == t.fill_batch_rows
    assert (first["tx_datetime_us"] // gen.US_PER_DAY).max() \
        == t.start_us // gen.US_PER_DAY - t.fill_batches
    fired = []
    w = t.window_source([(0.0, lambda: fired.append("open"))])
    served = []
    while (cols := w.poll_batch()) is not None:
        if cols:
            served.append(cols)
    assert fired == ["open"]
    ids = np.concatenate([c["tx_id"] for c in served])
    # every row due inside the window is served exactly once, in order
    assert np.array_equal(ids, t.n_fill + np.arange(len(t.due_s)))
    assert t.rows_due() == len(t.due_s) == w.rows_polled
    # no row is served before it is due
    for (rel, s, n) in w.polls:
        if rel < t.seconds:
            assert t.due_s[s + n - 1] <= rel
    look = t.lookup(ids)
    assert np.array_equal(look["tx_datetime_us"],
                          np.concatenate([c["tx_datetime_us"]
                                          for c in served]))
    q = t.queue_stats()
    assert q["poll_lag_p50_ms"] >= 0.0 and q["backlog_rows_end"] >= 0.0


def test_late_rows_are_refused_not_ignored():
    config, traffic = _files()
    with pytest.raises(ValueError):
        gen.build(dict(traffic, late_share=0.02), config, 1, 1.0, _decode)


def _poisson_traffic_files():
    import glob

    files = sorted(glob.glob(os.path.join(ROOT, "benchmark", "traffic",
                                          "*.json")))
    return [os.path.basename(f) for f in files
            if harness.load_json(f).get("arrivals") == "poisson"]


def _rate_faults(rate: float, pass_ms_by_bucket: dict, buckets) -> list:
    """What is wrong with an open-loop rate, given the measured time of one
    pass of the loop in each bucket of the ladder the rate can reach: the
    rows a pass collects must run a listed bucket, lie at least 20 % of a
    rung away from every rung (so that a pass a few ms longer or shorter
    runs the same program), and exactly one bucket may feed itself."""
    faults, own = [], []
    passes = {int(b): ms for b, ms in pass_ms_by_bucket.items()}
    for bucket, ms in sorted(passes.items()):
        rows = rate * ms / 1e3
        for rung in buckets:
            if abs(rows - rung) < 0.2 * rung:
                faults.append(f"a {ms} ms pass of the {bucket} bucket "
                              f"collects {rows:.0f} rows, within 20 % of "
                              f"the {rung} rung")
        runs = next((b for b in buckets if rows <= b), None)
        if runs not in passes:
            faults.append(f"{rows:.0f} rows run the {runs} bucket, which "
                          "has no measured pass time")
        if runs == bucket:
            own.append(bucket)
    if len(own) != 1:
        faults.append(f"buckets that feed themselves: {own}, not one")
    return faults


@pytest.mark.parametrize("name", _poisson_traffic_files())
def test_open_loop_rate_is_derived_and_has_one_state(name):
    """An open-loop mix states where its rate comes from (`derived_from`:
    knee, commit, date, share, the ladder and the measured pass of every
    bucket the rate can reach), the rate is share x knee rounded down to
    10,000, and the loop has one self-sustaining batch size at it. PR 23's
    170,000 rows/s fails this with the pass times read since PR 25 (see
    the next test): it sat 1.7 % above the 16,384 rung."""
    from real_time_fraud_detection_system_tpu.config import RuntimeConfig

    traffic = harness.load_json(os.path.join(ROOT, "benchmark", "traffic",
                                             name))
    d = traffic["derived_from"]
    for key in ("knee_rows_per_s", "knee_cell", "commit", "date", "share",
                "batch_buckets", "pass_ms_by_bucket"):
        assert key in d, key
    rate = traffic["rate_rows_per_s"]
    assert rate == int(d["share"] * d["knee_rows_per_s"] // 10_000) * 10_000
    assert 0.0 < d["share"] < 1.0
    buckets = RuntimeConfig().batch_buckets
    assert tuple(d["batch_buckets"]) == tuple(buckets)
    assert _rate_faults(rate, d["pass_ms_by_bucket"], buckets) == []


def test_the_rule_refuses_pr23s_rate_at_todays_pass_times():
    """170,000 rows/s with the passes PR 25 read (53.9 ms in the 16,384
    bucket, 98 ms in the 65,536 one): two buckets feed themselves and one
    sits on a rung. 0.8 of PR 23's knee at PR 23's ~301 ms pass was sound."""
    from real_time_fraud_detection_system_tpu.config import RuntimeConfig

    buckets = RuntimeConfig().batch_buckets
    faults = _rate_faults(170_000, {"16384": 53.9, "65536": 98.0}, buckets)
    assert any("16384 rung" in f for f in faults), faults
    assert any("[16384, 65536]" in f for f in faults), faults
    assert _rate_faults(170_000, {"65536": 301.0}, buckets) == []


def _draw_rule_traffic_files():
    import glob

    files = sorted(glob.glob(os.path.join(ROOT, "benchmark", "traffic",
                                          "*.json")))
    return [os.path.basename(f) for f in files
            if "draw_wraps" in harness.load_json(f).get("limits", {})]


def _draw_faults(traffic: dict, run_seconds: int) -> list:
    """What is wrong with a backlogged mix's draw under the rule its
    ``derived_from`` states: ``draw_rows`` = headroom x the ledger's
    highest ``rows_per_s`` of a cell on the file x ``run_seconds``,
    rounded up to a power of two, and a window that begins the draw again
    fails its run (``limits.draw_wraps`` 0)."""
    faults = []
    d = traffic.get("derived_from", {})
    for key in ("rule", "cell", "rows_per_s", "rows_per_s_source",
                "run_seconds", "headroom", "commit", "date"):
        if key not in d:
            faults.append(f"derived_from lacks {key}")
    if faults:
        return faults
    if traffic.get("limits", {}).get("draw_wraps") != 0:
        faults.append("limits.draw_wraps is not 0")
    if d["run_seconds"] != run_seconds:
        faults.append(f"derived for {d['run_seconds']} s, the benchmark "
                      f"runs {run_seconds}")
    if d["headroom"] < 1.5:
        faults.append(f"headroom {d['headroom']} under 1.5")
    need = d["headroom"] * d["rows_per_s"] * d["run_seconds"]
    want = 1 << int(np.ceil(np.log2(need)))
    if traffic["draw_rows"] != want:
        faults.append(f"draw_rows {traffic['draw_rows']}, the rule gives "
                      f"{want} for {need:.0f} rows")
    return faults


@pytest.mark.parametrize("name", _draw_rule_traffic_files())
def test_a_draw_that_carries_the_rule_outlasts_its_window(name):
    manifest = harness.load_manifest()
    traffic = harness.load_json(os.path.join(ROOT, "benchmark", "traffic",
                                             name))
    assert _draw_faults(traffic, manifest["run_seconds"]) == []
    users = [w["name"] for w in manifest["workloads"]
             if w["traffic"] + ".json" == name]
    assert traffic["derived_from"]["cell"] in users
    assert traffic["arrivals"] == "backlogged"


def test_the_rule_refuses_the_draw_the_exact_cell_had():
    """``saturate-active.json``'s 4,194,304 rows given the rule's block:
    64 batches where a window at the ledger's 474,270 rows/s polls ~145."""
    new = harness.load_json(os.path.join(ROOT, "benchmark", "traffic",
                                         "saturate-arriving.json"))
    old = harness.load_json(os.path.join(ROOT, "benchmark", "traffic",
                                         "saturate-active.json"))
    assert "derived_from" not in old and "limits" not in old
    given = dict(old, derived_from=new["derived_from"], limits=new["limits"])
    faults = _draw_faults(given, 20)
    assert len(faults) == 1 and "draw_rows 4194304" in faults[0], faults
    assert _draw_faults(dict(new, limits={}), 20) == [
        "limits.draw_wraps is not 0"]
    assert _draw_rule_traffic_files() == ["saturate-arriving.json"]
