"""Virtual-mesh scaling curve for the sharded serving engine.

Measures the sharded engine at mesh widths {1, 2, 4, 8} on the virtual
CPU mesh (``--xla_force_host_platform_device_count=8``), plus the
single-chip engine on the same stream as the reference row. All widths
execute on the SAME host cores, so wall-clock speedup is not the claim
— the claim this curve substantiates is that the shard_map machinery
(host partition/spill, packed per-chunk H2D, owner all_to_all,
re-assembly) does NOT compound with width: rows/s at a fixed total batch
should stay ≈flat from 1 → 8 devices, and width 1 should sit within a
few percent of the single-chip engine (the round-4 verdict's 29%
single-device tax, since removed via the identity owner-exchange and the
packed chunk transfer).

Prints ONE JSON line:

    {"total_rows": ..., "batches": ..., "model": ...,
     "single_chip_rows_per_s": ...,
     "by_devices": {"1": ..., "2": ..., "4": ..., "8": ...}}

This tool pins itself to the virtual CPU mesh: its figures are CPU
wall-clock, never device rates, and it is **not run on chip** (the
four-chip path is ``python chip_smoke.py --chips 4``). Run by hand:
``python tools/sharded_scaling_bench.py [--quick]``.
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


def _rand_batches(n_batches: int, rows: int, seed: int = 2) -> list:
    rng = np.random.default_rng(seed)
    out = []
    for b in range(n_batches):
        out.append({
            "tx_id": np.arange(b * rows, (b + 1) * rows, dtype=np.int64),
            "tx_datetime_us": (
                (20200 * 86400 + rng.integers(0, 86400, rows)).astype(
                    np.int64) * 1_000_000),
            "customer_id": rng.integers(0, 5000, rows).astype(np.int64),
            "terminal_id": rng.integers(0, 10000, rows).astype(np.int64),
            "tx_amount_cents": rng.integers(100, 50000, rows).astype(
                np.int64),
            "kafka_ts_ms": np.full(rows, b, dtype=np.int64),
        })
    return out


class _Replay:
    def __init__(self, batches):
        self._b = list(batches)
        self._i = 0
        self.offsets = [0]

    def poll_batch(self):
        if self._i >= len(self._b):
            return None
        b = self._b[self._i]
        self._i += 1
        self.offsets = [self._i]
        return b


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--rows", type=int, default=8192)
    ap.add_argument("--batches", type=int, default=6)
    args = ap.parse_args()

    import jax.numpy as jnp

    from real_time_fraud_detection_system_tpu.config import (
        Config,
        FeatureConfig,
        RuntimeConfig,
    )
    from real_time_fraud_detection_system_tpu.models.logreg import (
        init_logreg,
    )
    from real_time_fraud_detection_system_tpu.models.scaler import Scaler
    from real_time_fraud_detection_system_tpu.runtime import (
        ScoringEngine,
        ShardedScoringEngine,
    )

    rows = 2048 if args.quick else args.rows
    n_meas = 3 if args.quick else args.batches
    cfg = Config(
        features=FeatureConfig(customer_capacity=8192,
                               terminal_capacity=16384),
        runtime=RuntimeConfig(batch_buckets=(rows,), max_batch_rows=rows,
                              trigger_seconds=0.0, pipeline_depth=2),
    )
    params = init_logreg(15)
    scaler = Scaler(mean=jnp.zeros(15), scale=jnp.ones(15))

    def _measure(make_engine) -> float:
        e = make_engine()
        e.run(_Replay(_rand_batches(1, rows, seed=3)), trigger_seconds=0.0)
        s = e.run(_Replay(_rand_batches(n_meas, rows)),
                  trigger_seconds=0.0)
        return round(s["rows_per_s"], 1)

    result = {
        "total_rows": rows,
        "batches": n_meas,
        "model": "logreg",
        "host_cores": os.cpu_count(),
        "note": ("virtual 8-device CPU mesh on shared host cores: the "
                 "claim is flat rows/s across widths >= 2 (the "
                 "capacity-bounded owner exchange keeps TOTAL buffer "
                 "work ~2x batch regardless of width, so per-device "
                 "work shrinks as 1/width), not wall-clock speedup; "
                 "the 1 -> 2 step is the structural cost of turning "
                 "the routed exchange on"),
        "single_chip_rows_per_s": _measure(
            lambda: ScoringEngine(cfg, kind="logreg", params=params,
                                  scaler=scaler)),
        "by_devices": {},
    }
    for n_dev in (1, 2, 4, 8):
        # uniform 25% padding headroom at every width (pad = 1.25×rows),
        # so ordinary customer%n imbalance stays in one chunk and the
        # per-width numbers compare like for like
        rps = (rows * 5 // 4) // n_dev
        result["by_devices"][str(n_dev)] = _measure(
            lambda: ShardedScoringEngine(
                cfg, kind="logreg", params=params, scaler=scaler,
                n_devices=n_dev, rows_per_shard=rps))
        print(f"# devices={n_dev} -> "
              f"{result['by_devices'][str(n_dev)]} rows/s",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
