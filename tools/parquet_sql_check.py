"""Prove the ParquetSink output is standard-SQL-servable.

The reference wires Superset → Trino → Iceberg so analysts query the
``analyzed_transactions`` table with plain SQL (``superset/entrypoint.sh:19``,
``trino-config/catalog/nessie.properties:1-14``). This framework's claim is
that :class:`io.sink.ParquetSink` output is byte-compatible Parquet that any
such engine can mount. This script demonstrates it end to end, no container
stack required:

1. score a synthetic stream into a ParquetSink directory (or use
   ``--dir`` for an existing one);
2. mount the part files with a third-party SQL engine — DuckDB when
   installed (the engine that shares Trino's Parquet scan architecture),
   else pyarrow.dataset → an in-memory sqlite3 database (both ship with
   CPython/pyarrow, so this path is exercisable on any host);
3. run the dashboard's queries as REAL SQL (summary tiles, top-risky
   terminals, alert feed, per-day volumes — the io/query.py surface);
4. cross-check every number against io/query.py's own numpy answers and
   exit non-zero on any mismatch.

Prints one JSON line: ``{"ok": true, "engine": "duckdb"|"sqlite", ...}``.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import tempfile

import numpy as np

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# SQL texts shared by both engines (ANSI subset both speak). The table
# name `analyzed` is bound to the mounted Parquet data.
SQL_SUMMARY = """
SELECT COUNT(*)                                   AS transactions,
       COUNT(DISTINCT customer_id)                AS customers,
       COUNT(DISTINCT terminal_id)                AS terminals,
       SUM(tx_amount)                             AS total_amount,
       SUM(CASE WHEN prediction >= :thr THEN 1 ELSE 0 END) AS flagged,
       SUM(CASE WHEN prediction >= :thr THEN tx_amount ELSE 0 END)
                                                  AS flagged_amount,
       AVG(prediction)                            AS score_mean
FROM analyzed
"""

SQL_TOP_TERMINALS = """
SELECT terminal_id,
       COUNT(*)        AS transactions,
       AVG(prediction) AS mean_score
FROM analyzed
GROUP BY terminal_id
HAVING COUNT(*) >= :min_tx
ORDER BY mean_score DESC, terminal_id ASC
LIMIT :k
"""

SQL_ALERTS = """
SELECT tx_id, prediction
FROM analyzed
WHERE prediction >= :thr
ORDER BY tx_datetime_us DESC, tx_id DESC
LIMIT :k
"""

SQL_DAILY = """
SELECT CAST((tx_datetime_us - tx_datetime_us % 86400000000)
            / 86400000000 AS BIGINT)                AS day,
       COUNT(*)                                     AS transactions,
       SUM(tx_amount)                               AS amount
FROM analyzed
GROUP BY 1
ORDER BY 1
"""



def _bind(sql: str, params: dict) -> str:
    """Inline the (numeric-only) named parameters — one text for both
    engines without driver-specific placeholder styles."""
    for k, v in params.items():
        assert isinstance(v, (int, float))
        sql = sql.replace(f":{k}", repr(v))
    return sql




def _close(a, b, tol=1e-6) -> bool:
    if isinstance(a, float) or isinstance(b, float):
        return math.isfinite(float(a)) and math.isfinite(float(b)) \
            and abs(float(a) - float(b)) <= tol * max(1.0, abs(float(a)))
    return int(a) == int(b)


def _make_demo_dir(directory: str) -> None:
    """Tiny datagen → train → score → ParquetSink run (CPU-sized)."""
    from real_time_fraud_detection_system_tpu.config import (
        Config,
        DataConfig,
        FeatureConfig,
        TrainConfig,
    )
    from real_time_fraud_detection_system_tpu.data import generate_dataset
    from real_time_fraud_detection_system_tpu.io import ParquetSink
    from real_time_fraud_detection_system_tpu.models import train_model
    from real_time_fraud_detection_system_tpu.runtime import (
        ReplaySource,
        ScoringEngine,
    )
    from real_time_fraud_detection_system_tpu.utils.timing import (
        date_to_epoch_s,
    )

    cfg = Config(
        data=DataConfig(n_customers=80, n_terminals=160, n_days=40, seed=5),
        features=FeatureConfig(customer_capacity=128,
                               terminal_capacity=256),
        train=TrainConfig(delta_train_days=20, delta_delay_days=5,
                          delta_test_days=10, epochs=2),
    )
    _, _, txs = generate_dataset(cfg.data)
    model, _ = train_model(txs, cfg, kind="logreg")
    eng = ScoringEngine(cfg, kind="logreg", params=model.params,
                        scaler=model.scaler)
    eng.run(
        ReplaySource(txs, date_to_epoch_s(cfg.data.start_date),
                     batch_rows=2048),
        sink=ParquetSink(directory),
    )


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default=None,
                    help="existing ParquetSink directory (default: "
                         "generate a demo one)")
    ap.add_argument("--threshold", type=float, default=0.5)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--min-tx", type=int, default=3)
    args = ap.parse_args()

    tmp = None
    directory = args.dir
    if directory is None:
        tmp = tempfile.TemporaryDirectory(prefix="rtfds_sqlcheck_")
        directory = tmp.name
        _make_demo_dir(directory)

    queries = {
        "summary": _bind(SQL_SUMMARY, {"thr": args.threshold}),
        "top_terminals": _bind(SQL_TOP_TERMINALS,
                               {"min_tx": args.min_tx, "k": args.k}),
        # alert limit far above the flagged count: a LIMIT cutting inside
        # a timestamp tie would make row membership engine-dependent
        "alerts": _bind(SQL_ALERTS, {"thr": args.threshold, "k": 100000}),
        "daily": SQL_DAILY,
    }
    from real_time_fraud_detection_system_tpu.io.sqlquery import (
        run_queries,
    )

    engine, rows = run_queries(directory, queries)

    # ---- oracle: io/query.py over the same files --------------------
    from real_time_fraud_detection_system_tpu.io.query import (
        load_analyzed,
        recent_alerts,
        summary_stats,
        top_risky_terminals,
    )

    cols = load_analyzed(directory)
    mism = []

    s = summary_stats(cols, threshold=args.threshold)
    (got,) = rows["summary"]
    for i, key in enumerate(("transactions", "customers", "terminals",
                             "total_amount", "flagged", "flagged_amount",
                             "score_mean")):
        if not _close(got[i], s[key]):
            mism.append(f"summary.{key}: sql={got[i]} np={s[key]}")

    t = top_risky_terminals(cols, k=args.k, threshold=args.threshold,
                            min_transactions=args.min_tx)
    sql_terms = [r[0] for r in rows["top_terminals"]]
    # mean-score ties can order differently between engines — compare the
    # score sequence (must be identical) and the id SET
    sql_scores = [r[2] for r in rows["top_terminals"]]
    if not all(_close(a, b) for a, b in
               zip(sql_scores, t["mean_score"].tolist())):
        mism.append(f"top_terminals.scores: sql={sql_scores[:5]} "
                    f"np={t['mean_score'][:5]}")
    if len(sql_terms) != len(t["terminal_id"]):
        mism.append("top_terminals.len")

    a = recent_alerts(cols, threshold=args.threshold, limit=100000)
    sql_alert_ids = [r[0] for r in rows["alerts"]]
    if sorted(sql_alert_ids) != sorted(np.asarray(a["tx_id"]).tolist()):
        mism.append(f"alerts: sql={sql_alert_ids} np={a['tx_id']}")

    days = rows["daily"]
    np_days = cols["tx_datetime_us"] // 86_400_000_000
    uniq, cnt = np.unique(np_days, return_counts=True)
    if [int(r[0]) for r in days] != uniq.tolist() or \
            [int(r[1]) for r in days] != cnt.tolist():
        mism.append("daily volumes")

    out = {
        "ok": not mism,
        "engine": engine,
        "directory": directory if tmp is None else "<demo>",
        "rows": int(s["transactions"]),
        "queries": sorted(queries),
        "mismatches": mism,
    }
    print(json.dumps(out))
    if tmp is not None:
        tmp.cleanup()
    return 0 if not mism else 1


if __name__ == "__main__":
    sys.exit(main())
