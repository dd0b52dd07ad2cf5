"""The four device-contract checks.

Each check is a class with ``name``/``doc``/``severity-policy`` and a
``run(target, inventory, traced) -> findings`` where ``traced`` maps
``sig.key`` to the ``jax.jit(...).trace`` result for that signature
(or the exception tracing raised). Findings use the rtfdslint chassis
(fingerprint = rule + anchor path + context + message; context is the
signature's stable ``describe()`` label, so a baseline entry pins one
signature's verdict without line numbers).
"""

from __future__ import annotations

from typing import Iterable, List

from rtfdslint.finding import Finding

from . import jaxpr_walk as jw
from .targets import VerifyTarget

#: check registry (mirrors rtfdslint.registry, scoped to this package)
_CHECKS: List[type] = []


def register(cls: type) -> type:
    _CHECKS.append(cls)
    return cls


def all_checks() -> List[type]:
    return list(_CHECKS)


def known_check_names() -> set:
    return {c.name for c in _CHECKS}


def _f(check: str, severity: str, target: VerifyTarget, message: str,
       context: str = "") -> Finding:
    return Finding(rule=check, severity=severity, path=target.anchor,
                   line=target.line, message=message,
                   context=context or target.name)


def _jaxpr_of(traced):
    return traced.jaxpr  # jax.stages.Traced


@register
class AotCoverageCheck:
    """Prove warmup coverage: no reachable dispatch key outside the
    inventory, every inventory signature traces, no dead executables."""

    name = "aot-coverage"
    doc = ("every runtime-reachable dispatch signature is in the "
           "inventory precompile() compiles, and traces to a lowerable "
           "program — a mid-stream recompile is impossible by "
           "construction")

    def run(self, target: VerifyTarget, inventory, traced
            ) -> Iterable[Finding]:
        out: List[Finding] = []
        eng = target.engine
        keys = [sig.key for sig in inventory]
        if len(set(keys)) != len(keys):
            out.append(_f(self.name, "P0", target,
                          "duplicate dispatch keys in the inventory — "
                          "precompile() would silently skip one variant"))
        # Reachable keys, derived INDEPENDENTLY from the dispatch-site
        # contract (engine.py::_start_batch keys on ("step", 7, pad)
        # with pad from core.batch.bucket_size — 9 rows at key_bits=64,
        # both words of the two keys; the sharded engine on
        # ("sharded", routed)) — the inventory must cover them, and the
        # derivation deliberately does NOT call dispatch_inventory(), so
        # a drifted enumeration cannot vacuously agree with itself.
        sharded = hasattr(eng, "rows_per_shard")
        if sharded:
            expected = {("sharded", False), ("sharded", True)} \
                if eng.kind != "sequence" else set()
        else:
            rows = 9 if getattr(eng.cfg.features, "key_bits", 32) == 64 \
                else 7
            expected = {
                ("step", rows, int(b))
                for b in sorted(set(eng.cfg.runtime.batch_buckets))
            }
        fcfg = eng.cfg.features
        if (eng.kind != "sequence"
                and getattr(fcfg, "key_mode", "") == "exact"
                and getattr(fcfg, "compact_every", 0) > 0):
            # engine.py::_maybe_compact dispatches the recency-
            # compaction pass under this key on its batch cadence —
            # single-chip AND sharded (the mesh engine swaps in the
            # shard_map'd per-shard pass under the same key)
            expected.add(("compact",))
            if getattr(fcfg, "cold_store", ""):
                # engine.py::_promote_returning dispatches a returning
                # key's rows ahead of the step that scores it, under a
                # key a table with a directory and a width of the lane
                # ladder (same single-chip/sharded split as compact) —
                # a returning key must never pay a mid-stream compile
                from real_time_fraud_detection_system_tpu.runtime.engine \
                    import promote_widths

                tables = (("terminal",)
                          if fcfg.customer_source == "cms"
                          else ("customer", "terminal"))
                expected.update(
                    ("promote", t, w) for t in tables
                    for w in promote_widths(
                        max(eng.cfg.runtime.batch_buckets)))
        for key in sorted(expected - set(keys), key=str):
            out.append(_f(
                self.name, "P0", target,
                f"uncovered dispatch signature {key}: the runtime can "
                "dispatch this key but dispatch_inventory() does not "
                "enumerate it — precompile() will never compile it and "
                "the first touch pays a mid-stream XLA compile"))
        for key in sorted(set(keys) - expected, key=str):
            out.append(_f(
                self.name, "P2", target,
                f"inventory signature {key} is not reachable from any "
                "dispatch site — precompile() compiles a dead "
                "executable (wasted warmup time and cache space)"))
        for sig in inventory:
            tr = traced.get(sig.key)
            if isinstance(tr, Exception):
                out.append(_f(
                    self.name, "P0", target,
                    f"signature fails to trace: {type(tr).__name__}: "
                    f"{str(tr)[:200]} — the warmup path would crash (or "
                    "skip) and serving would pay the failure mid-stream",
                    context=sig.describe()))
        return out


@register
class ZModeExactnessCheck:
    """The PR-9 exactness contract, structurally: walk every
    ``dot_general``/``convert_element_type`` in the traced scoring
    program and prove the dtype lattice.

    What is walked is the program as traced HERE, off the chip, where
    the decision projection is the f32-HIGHEST contraction. On a TPU
    ``models/forest._selector`` picks the projection's other exact form,
    x in three bfloat16 parts through one bf16 pass (PR 47): its
    losslessness is a VALUE fact (``h + m + l == x``) no dtype lattice
    can carry — ``tests/test_zmode.py`` holds it on inputs drawn to break
    it and ``tests/test_tpu_compile.py`` reads the compiled contraction."""

    name = "zmode-exactness"
    doc = ("int8/bf16 z arithmetic stays exact by construction: integer "
           "z contraction survives, every float contraction (the decision "
           "projection; the leaf pick is a select) stays f32-HIGHEST, and "
           "no laundered downcast enters the scoring "
           "program")

    #: dots whose operands are provably tiny integers (bool-derived
    #: lhs) are exact in ANY precision/dtype — everything else must be
    #: f32 pinned to HIGHEST.
    def run(self, target: VerifyTarget, inventory, traced
            ) -> Iterable[Finding]:
        out: List[Finding] = []
        for sig in inventory:
            if sig.z_mode is None:
                continue  # non-ensemble kinds carry no z contraction
            tr = traced.get(sig.key)
            if tr is None or isinstance(tr, Exception):
                continue  # aot-coverage already flagged it
            jaxpr = _jaxpr_of(tr)
            ctx = sig.describe()
            dts = jw.dtypes_used(jaxpr)
            if "float64" in dts:
                out.append(_f(
                    self.name, "P0", target,
                    "float64 aval in the traced step — the exactness "
                    "contract is defined over f32 decisions (and x64 "
                    "doubles every transfer)", context=ctx))
            # Laundered downcast: a reduced-precision float anywhere in
            # the int8 scoring program breaks bit-identity with f32; in
            # bf16/f32 modes a downcast is legal ONLY on the emission
            # tail (emit_dtype) or with bool-derived provenance.
            if sig.z_mode == "int8" and sig.emit_dtype == "float32":
                for bad in sorted(dts & {"bfloat16", "float16"}):
                    out.append(_f(
                        self.name, "P0", target,
                        f"{bad} aval in the int8-mode scoring program — "
                        "a laundered downcast breaks the int8≡f32 "
                        "bit-identity contract", context=ctx))
            else:
                # The bf16-emission license is bounded, not global: the
                # emission tail is exactly ONE f32→bf16 cast of the
                # outgoing feature matrix, so under emit_dtype=bfloat16
                # the FIRST non-exact narrowing is licensed and every
                # further one still flags (a jaxpr cannot say which
                # convert feeds the output, so one laundered cast can
                # hide behind the emission slot — documented
                # approximation; the runtime bit-identity tests stay
                # the backstop there).
                budget = 1 if sig.emit_dtype == "bfloat16" else 0
                for src, dst, exact in jw.converts_report(jaxpr):
                    if (src in ("float32", "float64")
                            and dst in ("bfloat16", "float16")
                            and not exact):
                        if budget > 0:
                            budget -= 1
                            continue
                        out.append(_f(
                            self.name, "P0", target,
                            f"{src}→{dst} convert of non-integer data in "
                            f"the {sig.z_mode} scoring program (only the "
                            "documented single emission downcast or "
                            "exact 0/1-derived operands may narrow)",
                            context=ctx))
            int_dots = 0
            for d in jw.dot_report(jaxpr):
                floats = {d["lhs_dtype"], d["rhs_dtype"], d["out_dtype"]}
                if not floats & {"float32", "float64", "bfloat16",
                                 "float16"}:
                    int_dots += 1  # integer in, integer out: exact
                    continue
                prec = d["precision"]
                pinned = prec is not None and all(
                    str(p).endswith("HIGHEST") for p in (
                        prec if isinstance(prec, tuple) else (prec,)))
                if pinned and d["lhs_dtype"] == d["rhs_dtype"] == \
                        "float32":
                    continue  # decision/leaf contraction, pinned
                if d["lhs_bool_derived"] or d["rhs_bool_derived"]:
                    # z contraction: the 0/1 decision matrix is one
                    # operand (einsum may place it on either side); the
                    # other is the ±1/0 path table, whose tiny-integer
                    # values to_gemm guarantees by construction — a
                    # VALUE fact the jaxpr cannot carry, so this license
                    # is deliberately one-sided (runtime bit-identity
                    # tests stay the backstop for the table side)
                    continue
                out.append(_f(
                    self.name, "P0", target,
                    f"unpinned contraction {d['lhs_dtype']}×"
                    f"{d['rhs_dtype']}→{d['out_dtype']} "
                    f"(precision={d['precision']}) with non-integer "
                    "operands — decisions can flip under reduced "
                    "precision (the contract pins these to f32-HIGHEST)",
                    context=ctx))
            if sig.z_mode == "int8" and int_dots == 0:
                out.append(_f(
                    self.name, "P0", target,
                    "z_mode=int8 but no integer contraction survives in "
                    "the traced program — the int8 path was silently "
                    "degraded to float arithmetic", context=ctx))
        return out


@register
class DonationSafetyCheck:
    """Donated buffers: only the feature state, never under the
    nan-guard, matching what the jit actually declares, and every
    donated leaf can alias an output."""

    name = "donation-safety"
    doc = ("buffer donation donates exactly the feature state (arg 0), "
           "is OFF under the nan-guard (its rollback re-reads pre-batch "
           "state host-side), matches the traced jit's declaration, and "
           "every donated leaf finds a shape/dtype-matching output to "
           "alias")

    def run(self, target: VerifyTarget, inventory, traced
            ) -> Iterable[Finding]:
        out: List[Finding] = []
        eng = target.engine
        for sig in inventory:
            ctx = sig.describe()
            if eng.cfg.runtime.nan_guard and sig.donate:
                out.append(_f(
                    self.name, "P0", target,
                    "nan_guard is on but the step donates "
                    f"argnums {sig.donate}: the guard's rollback "
                    "re-reads the pre-batch state AFTER dispatch — a "
                    "donated buffer is deleted by then", context=ctx))
            extra = [a for a in sig.donate if a != 0]
            if extra:
                out.append(_f(
                    self.name, "P0", target,
                    f"step donates argnums {tuple(extra)} beyond the "
                    "feature state: params/scaler/batch are re-read "
                    "host-side (checkpoint save, _params_sig, feedback) "
                    "after dispatch", context=ctx))
            tr = traced.get(sig.key)
            if tr is None or isinstance(tr, Exception):
                continue
            # Traced.donate_argnums is FLATTENED (leaf indices); expand
            # the inventory's tree-level claim to the same coordinates.
            import jax

            args = eng.signature_templates(sig)
            offsets, n = [], 0
            for a in args:
                offsets.append(n)
                n += len(jax.tree.leaves(a))
            expect_flat = tuple(sorted(
                i
                for argnum in sig.donate
                for i in range(
                    offsets[argnum],
                    offsets[argnum + 1] if argnum + 1 < len(offsets)
                    else n)))
            declared = tuple(sorted(getattr(tr, "donate_argnums", ())
                                    or ()))
            if declared != expect_flat:
                out.append(_f(
                    self.name, "P0", target,
                    f"inventory claims donate={tuple(sorted(sig.donate))}"
                    f" (flat leaves {expect_flat}) but the traced jit "
                    f"declares {declared} — the inventory has drifted "
                    "from the live step", context=ctx))
            if declared:
                # every donated leaf must find a matching output aval,
                # else XLA silently keeps a copy (donation wasted)
                jaxpr = _jaxpr_of(tr)
                donated = [jaxpr.jaxpr.invars[i].aval for i in declared]
                outs = [v.aval for v in jaxpr.jaxpr.outvars]
                pool = [(getattr(a, "shape", None),
                         str(getattr(a, "dtype", ""))) for a in outs]
                for av in donated:
                    want = (getattr(av, "shape", None),
                            str(getattr(av, "dtype", "")))
                    if want in pool:
                        pool.remove(want)
                    else:
                        out.append(_f(
                            self.name, "P1", target,
                            f"donated feature-state leaf {want} has no "
                            "shape/dtype-matching output to alias — XLA "
                            "keeps a silent copy (donation wasted, "
                            "double HBM for that leaf)", context=ctx))
        return out


@register
class PallasAdmissionCheck:
    """VMEM budget + tile alignment for every signature with the fused
    Pallas path reachable, via the SAME ``admit_block`` predicate the
    engine's trace-time gate runs — plus trace-level agreement (a
    pallas_call is present iff admitted)."""

    name = "pallas-admission"
    doc = ("the tree-block TABLE bytes fit their VMEM budget and the "
           "padded layout tiles the MXU for every use_pallas signature, "
           "and the traced program agrees with the admission verdict "
           "(row tiles are not bounded here: the chip compiler's answer "
           "in tests/test_tpu_compile.py is that proof)")

    def run(self, target: VerifyTarget, inventory, traced
            ) -> Iterable[Finding]:
        out: List[Finding] = []
        eng = target.engine
        for sig in inventory:
            if not sig.use_pallas or sig.kind not in (
                    "tree", "forest", "gbt"):
                continue
            ctx = sig.describe()
            from real_time_fraud_detection_system_tpu.models.forest \
                import GemmEnsemble
            from real_time_fraud_detection_system_tpu.ops.pallas_forest \
                import admit_block
            from real_time_fraud_detection_system_tpu.runtime.engine \
                import _PALLAS_BLOCK_BUDGET

            params = eng.state.params
            trees = getattr(params, "trees", params)
            if not isinstance(trees, GemmEnsemble):
                out.append(_f(
                    self.name, "P1", target,
                    "use_pallas requested but the live ensemble is in "
                    "descent form (no GEMM tables) — the fused kernel "
                    "can never admit; serving falls back to XLA "
                    "silently", context=ctx))
                continue
            rec = admit_block(trees, sig.z_mode or "f32",
                              _PALLAS_BLOCK_BUDGET)
            # Non-vacuous alignment proof: admit_block re-derives the
            # padded layout with the same _ceil_to math to_pallas uses,
            # so its own tiles_aligned cannot fail unless the two
            # functions drift. Cross-check against the layout the
            # kernel table builder ACTUALLY produces (values are
            # irrelevant; template ensembles are tiny).
            from real_time_fraud_detection_system_tpu.ops.pallas_forest \
                import TREE_BLOCK, to_pallas

            pf = to_pallas(trees, sig.z_mode or "f32")
            tp, fp, ip = (int(d) for d in pf.sel.shape)
            lp = int(pf.path.shape[2])
            built = (tp, fp, ip, lp)
            aligned = (tp % TREE_BLOCK == 0 and fp % 8 == 0
                       and ip % 128 == 0 and lp % 128 == 0)
            if built != tuple(rec.padded):
                out.append(_f(
                    self.name, "P0", target,
                    f"admit_block's padded layout {tuple(rec.padded)} "
                    f"disagrees with the layout to_pallas builds "
                    f"{built} — the admission verdict is judging a "
                    "different kernel than the one that would serve",
                    context=ctx))
            if not (rec.tiles_aligned and aligned):
                out.append(_f(
                    self.name, "P0", target,
                    f"padded kernel layout {built} does not tile the "
                    "MXU/grid sizes — the pallas_call would fail or "
                    "mis-index at dispatch", context=ctx))
            if rec.block_bytes > rec.budget:
                out.append(_f(
                    self.name, "P0", target,
                    f"tree block needs {rec.block_bytes} bytes of VMEM "
                    f"against a {rec.budget}-byte budget — the fused "
                    "kernel cannot admit this ensemble (serving would "
                    "silently fall back to XLA; an unguarded kernel "
                    "would overflow VMEM)", context=ctx))
            tr = traced.get(sig.key)
            if tr is None or isinstance(tr, Exception):
                continue
            has_pallas = jw.has_primitive(_jaxpr_of(tr), "pallas_call")
            if rec.fits and not has_pallas:
                out.append(_f(
                    self.name, "P1", target,
                    "admission passes but no pallas_call appears in the "
                    "traced program — the fused path is gated off "
                    "somewhere else (the operator believes the kernel "
                    "serves; XLA does)", context=ctx))
            elif not rec.fits and has_pallas:
                out.append(_f(
                    self.name, "P0", target,
                    "admission FAILS but a pallas_call is traced anyway "
                    "— the VMEM gate is not protecting this program",
                    context=ctx))
        return out
