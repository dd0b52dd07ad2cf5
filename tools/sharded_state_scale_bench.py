"""Sharded tiered-feature-store scale matrix: shards × key universe.

The sharded half of ROADMAP item 2's proof shape: drive the SHARDED exact engine
(per-shard key directories + sketch replicas, ``key_mode="exact"``)
over a Zipf-skewed stream while the key universe grows 64k → 1M → 10M
with the hot tier FIXED, at 2 and 4 virtual devices, under
``--precompile``. The claims this matrix substantiates:

- rows/s at a 10M-key universe stays within ~10% of the SAME shard
  count's 64k baseline (state work is bounded by the working set, not
  the universe — the coordination cost stays flat as keys grow 1000×);
- zero mid-stream recompiles with per-shard compaction firing
  (``rtfds_xla_recompiles_total`` from the registry, not prints);
- per-shard dense hit rate and per-shard state bytes come from the
  REGISTRY series (``rtfds_feature_tier_rows_total{tier,shard}``,
  ``rtfds_feature_state_bytes{tier}``), the same numbers ``/healthz``
  serves.

All widths run on the same host cores (virtual CPU mesh), so the claim
is flat rows/s per width across universes — not wall-clock speedup.

This tool pins itself to the virtual CPU mesh: its figures are CPU
wall-clock, never device rates, and it is **not run on chip**.

Prints ONE JSON line. Run by hand:
``python tools/sharded_state_scale_bench.py [--quick]``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


class _ZipfSource:
    """Pre-generated Zipf micro-batches with the day advancing every few
    batches (so per-shard recency compaction has dead history to
    reclaim). Generation cost stays outside the measured loop."""

    def __init__(self, n_batches: int, rows: int, sampler, day_every: int,
                 seed: int = 2):
        from real_time_fraud_detection_system_tpu.data.generator import (
            zipf_stream_cols,
        )

        rng = np.random.default_rng(seed)
        self._batches = [
            zipf_stream_cols(rng, rows, sampler,
                             n_terminals=max(sampler.n_keys // 8, 64),
                             day=20200 + b // day_every,
                             tx_id_start=b * rows)
            for b in range(n_batches)
        ]
        self._i = 0

    def poll_batch(self):
        if self._i >= len(self._batches):
            return None
        b = self._batches[self._i]
        self._i += 1
        return b

    @property
    def offsets(self):
        return [self._i]

    def seek(self, offsets):
        self._i = int(offsets[0])


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--rows", type=int, default=16384)
    ap.add_argument("--batches", type=int, default=6)
    ap.add_argument("--shards", type=int, nargs="*", default=[2, 4])
    args = ap.parse_args()

    from real_time_fraud_detection_system_tpu.config import (
        Config,
        FeatureConfig,
        RuntimeConfig,
    )
    from real_time_fraud_detection_system_tpu.data.generator import (
        ZipfKeySampler,
    )
    from real_time_fraud_detection_system_tpu.models.logreg import (
        init_logreg,
    )
    from real_time_fraud_detection_system_tpu.models.scaler import Scaler
    from real_time_fraud_detection_system_tpu.runtime import (
        ShardedScoringEngine,
    )
    from real_time_fraud_detection_system_tpu.utils.metrics import (
        MetricsRegistry,
    )

    rows = 4096 if args.quick else args.rows
    n_meas = 3 if args.quick else args.batches
    skew = 1.1
    fcfg = FeatureConfig(
        key_mode="exact",
        customer_capacity=1 << 15,
        terminal_capacity=1 << 15,
        cms_width=1 << 14,
        compact_every=2,
    )
    cfg = Config(
        features=fcfg,
        runtime=RuntimeConfig(batch_buckets=(rows,), max_batch_rows=rows,
                              precompile=True),
    )
    params = init_logreg(15)
    scaler = Scaler(mean=np.zeros(15, np.float32),
                    scale=np.ones(15, np.float32))

    result = {
        "skew": skew,
        "batch_rows": rows,
        "batches": n_meas,
        "hot_tier_slots": fcfg.customer_capacity + fcfg.terminal_capacity,
        "host_cores": os.cpu_count(),
        "note": ("virtual CPU mesh on shared host cores: the claim is "
                 "flat rows/s per shard count as the universe grows "
                 "1000x (vs_64k within ~0.9), with per-shard hit rate "
                 "and state bytes from the registry"),
        "by_shards": {},
    }
    for n_dev in args.shards:
        if n_dev > jax.device_count():
            result["by_shards"][str(n_dev)] = {
                "skipped": f"needs {n_dev} devices, "
                           f"{jax.device_count()} visible"}
            continue
        cell: dict = {}
        base_rate = None
        for n_keys in (65536, 1 << 20, 10_000_000):
            sampler = ZipfKeySampler(n_keys, skew)
            reg = MetricsRegistry()
            eng = ShardedScoringEngine(
                cfg, kind="logreg", params=params, scaler=scaler,
                n_devices=n_dev, metrics=reg)
            eng.run(_ZipfSource(2, rows, sampler, day_every=1, seed=7))
            stats = eng.run(_ZipfSource(
                n_meas, rows, sampler,
                day_every=max(n_meas // 3, 1)))
            rate = stats["rows_per_s"]
            if base_rate is None:
                base_rate = rate
            per_shard_hit = {}
            for s in range(n_dev):
                d = reg.get("rtfds_feature_tier_rows_total",
                            tier="dense", shard=str(s))
                c = reg.get("rtfds_feature_tier_rows_total",
                            tier="cms", shard=str(s))
                dv = d.value if d is not None else 0.0
                cv = c.value if c is not None else 0.0
                per_shard_hit[str(s)] = (
                    round(dv / (dv + cv), 4) if dv + cv else 1.0)
            sb = {
                tier: reg.get("rtfds_feature_state_bytes",
                              tier=tier).value
                for tier in ("dense", "directory", "cms", "total")
            }
            rc = reg.get("rtfds_xla_recompiles_total")
            rec_rows = [
                v for labels, v in reg.family_series(
                    "rtfds_feature_slots_reclaimed_total")
                if "shard" in labels and labels.get("table") == "terminal"]
            cell[str(n_keys)] = {
                "rows_per_s": round(rate, 1),
                "vs_64k": (round(rate / base_rate, 3)
                           if base_rate else None),
                "dense_hit_rate_per_shard": per_shard_hit,
                "state_bytes_per_shard": {
                    k: int(v) // n_dev for k, v in sb.items()},
                "shards_reclaiming": sum(1 for v in rec_rows if v > 0),
                "mid_stream_recompiles": (rc.value if rc is not None
                                          else 0.0),
            }
            print(f"# shards={n_dev} universe={n_keys}: "
                  f"{cell[str(n_keys)]['rows_per_s']} rows/s "
                  f"(vs_64k {cell[str(n_keys)]['vs_64k']})",
                  file=sys.stderr, flush=True)
        cell["flat_within_10pct"] = all(
            u.get("vs_64k", 1.0) is None or u["vs_64k"] >= 0.9
            for u in cell.values() if isinstance(u, dict))
        result["by_shards"][str(n_dev)] = cell
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
