"""dispatch_inventory() ≡ what precompile() actually compiles.

The PR-11 acceptance bar: the inventory is the SINGLE enumeration of
the device plane's reachable programs — warmup compiles exactly it
(registry-counted via ``rtfds_precompiled_steps_total``), for both
engines, across z_modes and selective emission. A drifted inventory
here would make the verifier's coverage proof vacuous, so this file
pins the equivalence at runtime too.
"""

import dataclasses as dc
import re

import numpy as np
import pytest

from real_time_fraud_detection_system_tpu.config import (
    Config,
    FeatureConfig,
    RuntimeConfig,
)
from real_time_fraud_detection_system_tpu.features.spec import N_FEATURES
from real_time_fraud_detection_system_tpu.models.forest import (
    for_device,
    synthetic_ensemble,
)
from real_time_fraud_detection_system_tpu.models.scaler import Scaler
from real_time_fraud_detection_system_tpu.runtime.engine import (
    ScoringEngine,
)
from real_time_fraud_detection_system_tpu.utils.metrics import (
    MetricsRegistry,
)


def _cfg(**runtime_kw):
    return Config(
        features=FeatureConfig(customer_capacity=128,
                               terminal_capacity=256,
                               cms_width=1 << 10),
        runtime=dc.replace(
            RuntimeConfig(batch_buckets=(64, 256), max_batch_rows=256),
            **runtime_kw),
    )


def _scaler():
    return Scaler(mean=np.zeros(N_FEATURES, np.float32),
                  scale=np.ones(N_FEATURES, np.float32))


def _forest_params():
    return for_device(synthetic_ensemble(4, 3, N_FEATURES), N_FEATURES)


@pytest.mark.parametrize("z_mode,selective", [
    ("f32", False),
    ("int8", False),
    ("int8", True),
])
def test_single_engine_inventory_matches_precompile(z_mode, selective):
    reg = MetricsRegistry()
    cfg = _cfg(z_mode=z_mode,
               emit_threshold=0.9 if selective else 0.0)
    eng = ScoringEngine(cfg, "forest", _forest_params(), _scaler(),
                        metrics=reg)
    inv = eng.dispatch_inventory()
    assert [s.bucket for s in inv] == [64, 256]
    assert all(s.z_mode == z_mode for s in inv)
    assert all(s.selective == selective for s in inv)
    before = reg.get("rtfds_precompiled_steps_total").value
    eng.precompile()
    # registry-counted: one compiled executable per inventory signature
    assert reg.get("rtfds_precompiled_steps_total").value - before \
        == len(inv)
    assert sorted(eng._aot) == sorted(s.key for s in inv)
    # idempotent: a second precompile adds nothing
    eng.precompile()
    assert reg.get("rtfds_precompiled_steps_total").value - before \
        == len(inv)


def test_precompile_compiles_on_its_pool_what_one_after_another_would(
        monkeypatch):
    """``precompile()`` lowers on the calling thread and compiles on its
    pool: the same programs under the same ``_aot`` keys, counted once
    each, as ``lower().compile()`` one signature after another."""
    import threading

    import jax

    reg = MetricsRegistry()
    cfg = _cfg(z_mode="int8")
    cfg = dc.replace(cfg, runtime=dc.replace(
        cfg.runtime, batch_buckets=(16, 32, 64, 128, 256)))
    eng = ScoringEngine(cfg, "forest", _forest_params(), _scaler(),
                        metrics=reg)
    one_by_one = ScoringEngine(cfg, "forest", _forest_params(), _scaler(),
                               metrics=MetricsRegistry())
    inv = eng.dispatch_inventory()
    assert len(inv) == 5
    one_by_one.state.params = jax.tree.map(jax.numpy.asarray,
                                           one_by_one.state.params)

    def program(compiled):
        """The compiled computations without the call stacks that
        lowered them (this test's are not ``precompile()``'s)."""
        text = re.sub(r" stack_frame_id=\d+", "", compiled.as_text())
        return re.split(r"\n(?=%|ENTRY )", text, maxsplit=1)[1]

    want = {sig.key: program(one_by_one.signature_step(sig).lower(
        *one_by_one.signature_templates(sig)).compile())
        for sig in one_by_one.dispatch_inventory()}

    compiled_on = []
    compile_ = jax.stages.Lowered.compile

    def compile_and_note(self, *a, **kw):
        compiled_on.append(threading.current_thread().name)
        return compile_(self, *a, **kw)

    monkeypatch.setattr(jax.stages.Lowered, "compile", compile_and_note)
    man = eng.precompile()
    assert man["buckets"] == [16, 32, 64, 128, 256]
    assert len(compiled_on) == 5
    assert all(n.startswith("rtfds-compile") for n in compiled_on)
    assert list(eng._aot) == [sig.key for sig in inv]
    assert reg.get("rtfds_precompiled_steps_total").value == 5
    assert {k: program(c) for k, c in eng._aot.items()} == want
    assert not [t for t in threading.enumerate()
                if t.name.startswith("rtfds-compile")]


def test_precompile_raises_what_a_compile_raises(monkeypatch):
    """A compile that raises on the pool is raised from ``precompile()``;
    the signatures before it keep their executables, as they would one
    after another, and the pool's threads are gone."""
    import threading

    import jax

    reg = MetricsRegistry()
    eng = ScoringEngine(_cfg(), "forest", _forest_params(), _scaler(),
                        metrics=reg)
    compile_ = jax.stages.Lowered.compile

    def refuse_256(self, *a, **kw):
        if "tensor<7x256xi32>" in self.as_text():  # the packed batch
            raise RuntimeError("the compiler refuses this one")
        return compile_(self, *a, **kw)

    monkeypatch.setattr(jax.stages.Lowered, "compile", refuse_256)
    with pytest.raises(RuntimeError, match="refuses this one"):
        eng.precompile()
    assert list(eng._aot) == [("step", 7, 64)]
    assert reg.get("rtfds_precompiled_steps_total").value == 1
    assert not [t for t in threading.enumerate()
                if t.name.startswith("rtfds-compile")]
    monkeypatch.setattr(jax.stages.Lowered, "compile", compile_)
    eng.precompile()  # takes up where it stopped
    assert sorted(eng._aot) == [("step", 7, 64), ("step", 7, 256)]
    assert reg.get("rtfds_precompiled_steps_total").value == 2


def test_sharded_engine_inventory_matches_precompile():
    from real_time_fraud_detection_system_tpu.runtime.sharded_engine \
        import ShardedScoringEngine

    reg = MetricsRegistry()
    eng = ShardedScoringEngine(
        _cfg(z_mode="int8"), "forest", _forest_params(), _scaler(),
        n_devices=2, rows_per_shard=32, metrics=reg)
    inv = eng.dispatch_inventory()
    assert sorted(s.key for s in inv) == [("sharded", False),
                                          ("sharded", True)]
    assert all(s.bucket == 64 for s in inv)  # 2 devices × 32 rows
    before = reg.get("rtfds_precompiled_steps_total").value
    eng.precompile()
    assert reg.get("rtfds_precompiled_steps_total").value - before \
        == len(inv)
    assert sorted(eng._aot) == sorted(s.key for s in inv)
    # BOTH lazily-built variants exist now — no hot-key overflow can
    # pay a first compile mid-stream
    assert eng._sharded_step is not None
    assert eng._sharded_step_routed is not None
    # idempotent
    eng.precompile()
    assert reg.get("rtfds_precompiled_steps_total").value - before \
        == len(inv)


def test_sharded_sequence_inventory_is_empty():
    """kind='sequence' has no AOT path (pytree batches): the inventory
    says so, and precompile's manifest agrees."""
    from real_time_fraud_detection_system_tpu.models.sequence import (
        init_transformer,
    )
    from real_time_fraud_detection_system_tpu.runtime.sharded_engine \
        import ShardedScoringEngine

    cfg = _cfg()
    params = init_transformer(d_model=16, n_heads=2, n_layers=1,
                              d_ff=32)
    eng = ShardedScoringEngine(cfg, "sequence", params, _scaler(),
                               n_devices=2, rows_per_shard=32,
                               metrics=MetricsRegistry())
    assert eng.dispatch_inventory() == []
    assert eng.precompile().get("skipped") == "sequence"


def test_inventory_keys_are_the_runtime_dispatch_keys():
    """The key precompile() caches under is byte-identical to the key
    _dispatch_step looks up: ("step", 7, pad) from the packed batch's
    shape. A batch through every bucket must dispatch AOT (zero
    fallbacks), which is only true if the keys agree."""
    reg = MetricsRegistry()
    eng = ScoringEngine(_cfg(z_mode="f32"), "forest", _forest_params(),
                        _scaler(), metrics=reg)
    eng.precompile()
    rng = np.random.default_rng(0)
    for n in (10, 200):  # pads to 64 and 256
        cols = {
            "tx_id": np.arange(n, dtype=np.int64),
            "kafka_ts_ms": np.zeros(n, dtype=np.int64),
            "customer_id": rng.integers(0, 100, n).astype(np.int64),
            "terminal_id": rng.integers(0, 200, n).astype(np.int64),
            "tx_datetime_us": np.arange(n, dtype=np.int64) * 1_000_000,
            "tx_amount_cents": rng.integers(1, 10_000, n).astype(
                np.int64),
        }
        eng.process_batch(cols)
    assert reg.get("rtfds_aot_fallbacks_total").value == 0
    assert eng._aot, "fallback path silently dropped the AOT cache"


def test_exact_mode_inventory_enumerates_compact_variant():
    """key_mode='exact' + compact_every adds the recency-compaction pass
    as its own signature; precompile compiles it with the buckets (the
    registry count proves it), and the variant carries no z contraction
    or Pallas claim for the per-signature checks to misfire on."""
    import dataclasses as _dc

    reg = MetricsRegistry()
    cfg = _cfg()
    cfg = cfg.replace(features=_dc.replace(
        cfg.features, key_mode="exact", compact_every=4))
    eng = ScoringEngine(cfg, "forest", _forest_params(), _scaler(),
                        metrics=reg)
    inv = eng.dispatch_inventory()
    assert [s.key for s in inv] == [("step", 7, 64), ("step", 7, 256),
                                    ("compact",)]
    compact = inv[-1]
    assert compact.variant == "compact"
    assert compact.z_mode is None and not compact.use_pallas
    eng.precompile()
    assert reg.get("rtfds_precompiled_steps_total").value == len(inv)
    assert sorted(eng._aot) == sorted(s.key for s in inv)
    # compaction off -> no compact signature (and no dead executable)
    cfg2 = _cfg().replace(features=_dc.replace(
        _cfg().features, key_mode="exact", compact_every=0))
    eng2 = ScoringEngine(cfg2, "forest", _forest_params(), _scaler(),
                         metrics=MetricsRegistry())
    assert [s.key for s in eng2.dispatch_inventory()] \
        == [("step", 7, 64), ("step", 7, 256)]


def test_sharded_exact_inventory_enumerates_compact_variant():
    """The sharded engine's exact-mode inventory carries the per-shard
    compaction signature beside both step variants; precompile compiles
    all three (registry-counted), and the serving keys agree."""
    import dataclasses as _dc

    from real_time_fraud_detection_system_tpu.runtime.sharded_engine \
        import ShardedScoringEngine

    reg = MetricsRegistry()
    cfg = _cfg()
    cfg = cfg.replace(features=_dc.replace(
        cfg.features, key_mode="exact", compact_every=4))
    eng = ShardedScoringEngine(
        cfg, "forest", _forest_params(), _scaler(),
        n_devices=2, rows_per_shard=32, metrics=reg)
    inv = eng.dispatch_inventory()
    assert sorted((s.key for s in inv), key=str) == sorted(
        [("sharded", False), ("sharded", True), ("compact",)], key=str)
    compact = [s for s in inv if s.variant == "compact"][0]
    assert compact.z_mode is None and not compact.use_pallas
    before = reg.get("rtfds_precompiled_steps_total").value
    eng.precompile()
    assert reg.get("rtfds_precompiled_steps_total").value - before \
        == len(inv)
    assert sorted(eng._aot, key=str) == sorted(
        (s.key for s in inv), key=str)
    # idempotent
    eng.precompile()
    assert reg.get("rtfds_precompiled_steps_total").value - before \
        == len(inv)
