"""Tree-ensemble inference on TPU — the reference's flagship model family.

The reference's production scorer is a pickled sklearn RandomForest applied
row-wise in a pandas UDF (``fraud_detection.py:183-195``;
``model_training.ipynb · cell 59`` picks the RF as ``trained_model.pkl``).
A branchy per-row tree walk is hostile to TPU, so inference is re-cast as a
**vectorized level-synchronous descent**: all B rows × T trees advance one
level per step with three flat gathers (feature id, threshold, children) and
a select — no data-dependent control flow, `lax.fori_loop` over max_depth
steps, leaves self-loop so ragged depths need no masking. Exact (bit-equal
decisions vs sklearn on f32 inputs) and O(B·T·depth) work instead of the
O(B·T·nodes·leaves) FLOP inflation of the matmul ("Hummingbird GEMM")
formulation — which is also provided (:func:`to_gemm`,
:func:`gemm_predict_proba`) for MXU-utilization experiments.

Training stays on host (sklearn, mirroring the reference's offline
notebook); the fitted estimator compiles once into flat node tables shipped
to HBM. Trees must be depth-bounded to give the loop a static trip count
(config ``model.forest_max_depth``) — a documented deviation from the
reference's unbounded-depth RF, with equivalent accuracy on this data.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from real_time_fraud_detection_system_tpu.ops.numerics import sum_fixed_order
from real_time_fraud_detection_system_tpu.utils.trace import step_scope


class TreeEnsemble(NamedTuple):
    """Flat node tables, padded to (T trees × N nodes). Leaves self-loop."""

    feat: jnp.ndarray  # int32 [T, N] — feature index tested at node (0 at leaves)
    thresh: jnp.ndarray  # float32 [T, N] — go left iff x[feat] <= thresh
    left: jnp.ndarray  # int32 [T, N] — left child (node itself at leaves)
    right: jnp.ndarray  # int32 [T, N]
    prob: jnp.ndarray  # float32 [T, N] — P(class 1) at node (leaves used)
    max_depth: int  # static trip count for the descent loop

    @property
    def n_trees(self) -> int:
        return int(self.feat.shape[0])


def ftz_safe_thresholds(t32: np.ndarray) -> np.ndarray:
    """Replace denormal thresholds with their flush-to-zero-safe stand-in.

    XLA (TPU and CPU) flushes f32 denormals to zero in comparisons, so a
    threshold like ``-1e-45`` — which ``nextafter``-below-0.0 produces —
    behaves as ``-0.0`` and flips ``x <= thresh`` for ``x == 0.0``
    exactly. Under FTZ the representable inputs are normals and zero, so
    the exact stand-ins are: positive denormal → ``0.0`` (x <= denorm ⟺
    x <= 0), negative denormal → ``-FLT_MIN`` (x <= -denorm ⟺ x < 0 ⟺
    x <= -smallest-normal). Found by the randomized xgboost-dump parity
    test (a split_condition of exactly 0.0 routed wrong).

    Caveat (non-FTZ backends): the stand-ins are exact only when the
    comparison INPUTS are normals or zero — true under FTZ, where
    denormal features cannot reach the comparator. On a backend that
    does NOT flush denormals in comparisons, a denormal input
    ``x ∈ (-FLT_MIN, 0)`` routes differently against the ``-FLT_MIN``
    stand-in (``x <= -FLT_MIN`` is False though ``x < 0``) than it did
    against the original ``nextafter`` threshold. Accepted tradeoff: the
    engineered features (counts, averages of cent-quantized amounts,
    risk ratios) make denormal inputs practically impossible."""
    t32 = np.asarray(t32, dtype=np.float32).copy()
    tiny = np.float32(np.finfo(np.float32).tiny)
    denorm = (t32 != 0.0) & (np.abs(t32) < tiny)
    t32[denorm & (t32 > 0)] = np.float32(0.0)
    t32[denorm & (t32 < 0)] = -tiny
    return t32


def _f32_round_down(t64: np.ndarray) -> np.ndarray:
    """Round float64 thresholds DOWN to float32 so that for any f32 input x:
    (x <= t32) == (x <= t64) — decisions stay bit-identical to sklearn on
    f32-quantized features."""
    t32 = t64.astype(np.float32)
    over = t32.astype(np.float64) > t64
    t32[over] = np.nextafter(t32[over], np.float32(-np.inf), dtype=np.float32)
    return ftz_safe_thresholds(t32)


def ensemble_from_sklearn(model, n_features: int) -> TreeEnsemble:
    """Compile a fitted sklearn DecisionTree/RandomForest/ExtraTrees into
    flat node tables."""
    trees = getattr(model, "estimators_", None)
    if trees is None:
        trees = [model]
    else:
        trees = [t for t in np.asarray(trees).ravel()]

    T = len(trees)
    N = max(t.tree_.node_count for t in trees)
    feat = np.zeros((T, N), dtype=np.int32)
    thresh = np.zeros((T, N), dtype=np.float32)
    left = np.zeros((T, N), dtype=np.int32)
    right = np.zeros((T, N), dtype=np.int32)
    prob = np.zeros((T, N), dtype=np.float32)
    depth = 0
    for ti, est in enumerate(trees):
        tr = est.tree_
        n = tr.node_count
        is_leaf = tr.children_left == -1
        feat[ti, :n] = np.where(is_leaf, 0, tr.feature)
        thresh[ti, :n] = _f32_round_down(np.where(is_leaf, 0.0, tr.threshold))
        idx = np.arange(n, dtype=np.int32)
        left[ti, :n] = np.where(is_leaf, idx, tr.children_left).astype(np.int32)
        right[ti, :n] = np.where(is_leaf, idx, tr.children_right).astype(np.int32)
        v = tr.value[:, 0, :]  # [n, n_classes] (fractions or counts)
        if v.shape[1] > 1:
            tot = v.sum(axis=1)
            prob[ti, :n] = np.where(tot > 0, v[:, -1] / np.maximum(tot, 1e-12), 0.0)
        else:
            prob[ti, :n] = v[:, 0]
        depth = max(depth, int(tr.max_depth))
    return TreeEnsemble(
        feat=jnp.asarray(feat),
        thresh=jnp.asarray(thresh),
        left=jnp.asarray(left),
        right=jnp.asarray(right),
        prob=jnp.asarray(prob),
        max_depth=depth,
    )


def ensemble_leaf_values(ens: TreeEnsemble, x: jnp.ndarray) -> jnp.ndarray:
    """[B, F] → per-tree leaf value [B, T].

    Level-synchronous descent: node[b,t] advances one level per iteration;
    leaves self-loop, so ``max_depth`` iterations land every lane on its
    leaf. Three gathers + one compare + one select per step, all [B, T].
    """
    b = x.shape[0]
    t, n = ens.feat.shape
    tree_base = (jnp.arange(t, dtype=jnp.int32) * n)[None, :]  # [1, T]
    feat = ens.feat.reshape(-1)
    thresh = ens.thresh.reshape(-1)
    left = ens.left.reshape(-1)
    right = ens.right.reshape(-1)

    def body(_, node):
        flat = tree_base + node  # [B, T]
        f = feat[flat]
        xv = jnp.take_along_axis(x, f, axis=1)  # [B, T]
        go_left = xv <= thresh[flat]
        return jnp.where(go_left, left[flat], right[flat])

    node0 = jnp.zeros((b, t), dtype=jnp.int32)
    node = jax.lax.fori_loop(0, ens.max_depth, body, node0)
    return ens.prob.reshape(-1)[tree_base + node]  # [B, T]


def ensemble_predict_proba(ens: TreeEnsemble, x: jnp.ndarray) -> jnp.ndarray:
    """[B, F] → fraud probability [B] (bagging: mean of per-tree probs)."""
    return jnp.mean(ensemble_leaf_values(ens, x), axis=1)


class GemmEnsemble(NamedTuple):
    """Matmul ("Hummingbird GEMM") formulation — see :func:`to_gemm`."""

    sel: jnp.ndarray  # float32 [T, F, I] one-hot feature selector per node
    thresh: jnp.ndarray  # float32 [T, I]
    path: jnp.ndarray  # float32 [T, I, L] — +1 left-required, -1 right, 0 off-path
    target: jnp.ndarray  # float32 [T, L] — #left-required per leaf (pad 1e9)
    leaf_val: jnp.ndarray  # float32 [T, L]

    @property
    def n_trees(self) -> int:
        return int(self.sel.shape[0])


def to_gemm(ens: TreeEnsemble, n_features: int) -> GemmEnsemble:
    """Compile node tables into the 3-matmul formulation.

    Leaf l is reached iff every on-path node decision matches; with the ±1
    path encoding, Z[l] = Σ path[i,l]·D[i] equals target[l] (= #left-required)
    exactly in that case and only then.
    """
    feat = np.asarray(ens.feat)
    thresh = np.asarray(ens.thresh)
    left = np.asarray(ens.left)
    right = np.asarray(ens.right)
    prob = np.asarray(ens.prob)
    T, N = feat.shape

    per_tree = []
    for t in range(T):
        is_leaf = left[t] == np.arange(N)
        # restrict to reachable nodes of this tree (padding is unreachable)
        internal = []
        leaves = []
        stack = [0]
        seen = set()
        while stack:
            nd = stack.pop()
            if nd in seen:
                continue
            seen.add(nd)
            if is_leaf[nd]:
                leaves.append(nd)
            else:
                internal.append(nd)
                stack.append(int(left[t, nd]))
                stack.append(int(right[t, nd]))
        i_of = {nd: i for i, nd in enumerate(sorted(internal))}
        l_of = {nd: i for i, nd in enumerate(sorted(leaves))}
        I, L = len(internal), len(leaves)
        sel = np.zeros((n_features, max(I, 1)), dtype=np.float32)
        th = np.full(max(I, 1), np.float32(np.inf))
        path = np.zeros((max(I, 1), max(L, 1)), dtype=np.float32)
        target = np.zeros(max(L, 1), dtype=np.float32)
        leaf_val = np.zeros(max(L, 1), dtype=np.float32)
        # iterative root→leaf walk collecting requirements
        stack2 = [(0, [])]
        while stack2:
            nd, req = stack2.pop()
            if is_leaf[nd]:
                li = l_of[nd]
                for i, sign in req:
                    path[i, li] = sign
                target[li] = sum(1 for _, s in req if s > 0)
                leaf_val[li] = prob[t, nd]
            else:
                i = i_of[nd]
                sel[feat[t, nd], i] = 1.0
                th[i] = thresh[t, nd]
                stack2.append((int(left[t, nd]), req + [(i, +1)]))
                stack2.append((int(right[t, nd]), req + [(i, -1)]))
        per_tree.append((sel, th, path, target, leaf_val))

    I = max(p[0].shape[1] for p in per_tree)
    L = max(p[2].shape[1] for p in per_tree)
    F = n_features
    sel = np.zeros((T, F, I), dtype=np.float32)
    th = np.full((T, I), np.float32(np.inf))
    path = np.zeros((T, I, L), dtype=np.float32)
    target = np.full((T, L), 1e9, dtype=np.float32)
    leaf_val = np.zeros((T, L), dtype=np.float32)
    for t, (s, t_, p, tg, lv) in enumerate(per_tree):
        i, l = s.shape[1], p.shape[1]
        sel[t, :, :i] = s
        th[t, :i] = t_
        path[t, :i, :l] = p
        target[t, :l] = tg
        leaf_val[t, :l] = lv
    return GemmEnsemble(
        sel=jnp.asarray(sel), thresh=jnp.asarray(th), path=jnp.asarray(path),
        target=jnp.asarray(target), leaf_val=jnp.asarray(leaf_val),
    )


def resolve_z_mode(mode: str | None) -> str:
    """``RuntimeConfig.z_mode`` → a concrete :func:`gemm_leaf_sum` mode.

    ``"auto"`` (and None) picks int8 on TPU — int8 peaks ~2× bf16 on
    v5e and is bit-equal to f32; in the served forest the three modes
    tie on the chip (ROADMAP C4) — and f32 elsewhere (the only
    float mode CPU XLA lowers natively). Every mode is decision-exact by
    the contract documented on :func:`gemm_leaf_sum`; int8 is
    additionally BIT-identical to f32 (integer z arithmetic, same
    leaf match, same pinned-order leaf sum)."""
    if mode is None or mode == "auto":
        return "int8" if jax.default_backend() == "tpu" else "f32"
    if mode not in ("f32", "bf16", "int8"):
        raise ValueError(f"unknown z_mode {mode!r}")
    return mode


def gemm_leaf_sum(
    g: GemmEnsemble, x: jnp.ndarray, z_mode: str | None = None
) -> jnp.ndarray:
    """[B, F] → Σ_t leaf value [B] via three contractions (MXU formulation).

    Sum-reduction shared by bagging (÷ n_trees) and boosting (+ base logit).
    Two named parts (``utils/trace.STEP_SCOPES``): ``decide`` — the
    selector contraction and the threshold compare — and ``leaves`` — the
    z contraction, the leaf match and select, the pinned-order sum.

    Mixed precision, chosen to stay bit-exact (verified on v5e: max |Δ| = 0
    vs all-HIGHEST, incl. inputs placed exactly on thresholds):

    - the decision ``proj <= thresh`` needs ``proj[b, t, i]`` to BE the f32
      value ``x[b, feat(t, i)]``: a pass that rounds a feature to bf16
      flips decisions near thresholds (measured: ``HIGH`` flips ~1% of
      decisions on threshold-valued inputs). ``sel`` is one-hot, so the
      contraction only has to carry x through unrounded, and two forms do
      (:func:`_selector` picks by backend, the only thing it looks at):
        * on TPU, **one bf16 pass with an f32 accumulator**: x is split
          into three bfloat16 parts whose sum is x to the bit
          (:func:`split_bf16x3`), the parts lie side by side along the
          contraction axis (``[B, 3F]``, 45 deep in an array 128 deep) and
          meet the 0/1 selector repeated three times. Every product is a
          bf16 value times 0 or 1 and every partial sum of the three
          non-zero terms is an f32 value, so the accumulator holds x
          exactly. f32 ``HIGHEST`` did the same split inside the compiler
          and spent six passes on it, three of them on the selector's
          zero low parts: 18.3 → 7.2 ms a 65,536-row step (PERF.md §6,
          PR 47).
        * elsewhere, the f32 contraction: CPU XLA multiplies f32 by f32
          exactly (x·1 + Σ 0), and has no use for the split.
    - the dominant z contraction is exact in EVERY reduced-precision mode
      because its operands are tiny integers: d is 0/1, path is ±1/0, and
      z counts ≤ depth. ``z_mode`` selects the arithmetic:
        * ``"bf16"`` — bf16×bf16→f32 (integers ≪ 2^8 are bf16-exact);
          ~15% faster than f32 end-to-end on v5e. TPU default.
        * ``"int8"`` — int8×int8→int32 on the MXU's int8 path (2× bf16
          peak on v5e); ``target`` compares exactly in int32, with the
          1e9 leaf padding still unmatched.
        * ``"f32"`` — plain f32; the only float mode CPU XLA lowers
          (no BF16×BF16→F32 dot thunk there, so ``"bf16"`` silently
          degrades to f32 off-TPU — same values by construction).
          CPU default.
    - the leaf gather is a select, not a contraction: each tree's one
      matching leaf value is picked exactly (f32, no MXU pass), and the
      T per-tree values are added in a pinned order — the result does
      not depend on how a compiler tiles a reduction, so one chip and
      the mesh agree to the bit on equal features.
    """
    if z_mode is None:
        z_mode = "bf16" if jax.default_backend() == "tpu" else "f32"
    if z_mode not in ("bf16", "int8", "f32"):
        raise ValueError(f"unknown z_mode {z_mode!r}")
    with step_scope("decide"):
        pick = _selector(g.sel)  # once a call, not once a slab
    b = x.shape[0]
    if b <= LEAF_SLAB_ROWS:
        return _leaf_sum_slab(g, pick, x, z_mode)
    pad = -b % LEAF_SLAB_ROWS
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad, x.shape[1]), x.dtype)])
    slabs = x.reshape(-1, LEAF_SLAB_ROWS, x.shape[1])
    return jax.lax.map(lambda xs: _leaf_sum_slab(g, pick, xs, z_mode),
                       slabs).reshape(-1)[:b]


# Rows per pass of the three contractions. The [B, T, L] match tensor is
# never materialized, but the v5e compiler (libtpu 0.0.34) addresses it:
# once its f32 extent passed 2^31 bytes — 32,768 rows at T=100, L=256 —
# the fused [B, T, L] → [B, T] reduce returned wrong per-tree values for
# EVERY row (chip_smoke.py's oracle check: probabilities off by up to
# 0.73; a scratch run on the chip: right at 16,384 rows, wrong from
# 32,768, and right again over 8,192-row slabs at any batch size). No
# compile test and no CPU test can see that. Slabs also bound the step's
# temporaries, and measured 20% faster than one pass at 65,536 rows
# (19.2 ms vs 24.1 ms, same scratch run, PR 21). With the selector in
# one MXU pass (PR 47) the slab still hardly matters below the fault:
# 4,096 / 8,192 / 16,384 rows read 7.136 / 7.279 / 7.479 ms for 65,536
# rows on the chip, bit-equal answers — 8,192 stays (2% is 0.14 ms, and
# 16,384 is one doubling from the extent that returned wrong sums).
LEAF_SLAB_ROWS = 8192


def split_bf16x3(x: jnp.ndarray) -> jnp.ndarray:
    """f32 ``[B, F]`` → bfloat16 ``[B, 3F]``: ``h | m | l`` side by side,
    three parts of every value with ``(h + m) + l == x`` to the bit.

    Each part is the top eight significant bits of what is left, cut and
    not rounded (a rounded ``h`` of the largest finite f32 is inf): ``h``
    keeps the sign, the exponent and seven stored mantissa bits of x,
    ``x - h`` is exact in f32 (Sterbenz: same sign, no more than 16
    significant bits), ``m`` is its top eight, and ``l`` has eight or
    fewer left, which bfloat16 holds as they are. The parts have one sign
    and disjoint bits, so any sum of them, in any order, is an f32 value.
    Lossless for every finite f32 whose lowest set bit is worth 2^-126 or
    more (|x| ≥ 2^-103, and ±0): below that a low part is subnormal and a
    backend that flushes subnormals drops it, as f32 ``HIGHEST`` on the
    chip did."""
    cut = jnp.uint32(0xFFFF0000)

    def top(v):
        bits = jax.lax.bitcast_convert_type(v, jnp.uint32)
        return jax.lax.bitcast_convert_type(bits & cut, jnp.float32)

    h = top(x)
    m = top(x - h)
    l = x - h - m
    return jnp.concatenate([h, m, l], axis=1).astype(jnp.bfloat16)


def selector_bf16x3(sel: jnp.ndarray) -> jnp.ndarray:
    """One-hot f32 ``[T, F, I]`` → bfloat16 ``[T, 3F, I]``: ``sel`` (0/1:
    exact) three times along the contraction axis, one for each part of
    :func:`split_bf16x3`."""
    return jnp.tile(sel.astype(jnp.bfloat16), (1, 3, 1))


def _selector(sel: jnp.ndarray) -> jnp.ndarray:
    """The selector in the form this backend's contraction takes: on TPU
    :func:`selector_bf16x3`, elsewhere ``sel`` itself. Derived from the
    live params inside the jitted program, like ``to_pallas``'s tables — a
    checkpoint restore or a reload is served without a stale copy,
    ``GemmEnsemble`` and a checkpoint hold what they held, and model build
    runs no device program for it: a convert and a broadcast of 382,500
    entries a call, beside the slab loop."""
    return selector_bf16x3(sel) if jax.default_backend() == "tpu" else sel


def _project(pick: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """f32 ``[B, F]`` → f32 ``[B, T, I]``, ``x[b, feat(t, i)]`` to the bit
    (0 where a padding node selects nothing; a selected -0.0 comes out
    +0.0, the sum of it and the other features' zeros, which no compare
    tells apart). ``pick`` is :func:`_selector`'s form of the selector
    and says which contraction runs."""
    if pick.dtype == jnp.bfloat16:
        return jnp.einsum("bf,tfi->bti", split_bf16x3(x), pick,
                          preferred_element_type=jnp.float32)
    return jnp.einsum("bf,tfi->bti", x, pick,
                      precision=jax.lax.Precision.HIGHEST)


def _leaf_sum_slab(g: GemmEnsemble, pick: jnp.ndarray, x: jnp.ndarray,
                   z_mode: str):
    """:func:`gemm_leaf_sum` for one slab of at most ``LEAF_SLAB_ROWS``;
    ``pick`` is :func:`_selector`'s form of ``g.sel``."""
    with step_scope("decide"):
        go_left = _project(pick, x) <= g.thresh[None]
    with step_scope("leaves"):
        if z_mode == "int8":
            z = jnp.einsum(
                "bti,til->btl", go_left.astype(jnp.int8),
                g.path.astype(jnp.int8), preferred_element_type=jnp.int32,
            )
            match = z == g.target.astype(jnp.int32)[None]
        else:
            on_tpu = jax.default_backend() == "tpu"
            zdt = (jnp.bfloat16 if (z_mode == "bf16" and on_tpu)
                   else jnp.float32)
            z = jnp.einsum(
                "bti,til->btl", go_left.astype(zdt), g.path.astype(zdt),
                preferred_element_type=jnp.float32,
            )
            match = jnp.abs(z - g.target[None]) < 0.5
        # Exactly one leaf per tree matches, so the sum over L adds zeros
        # to one value: exact in any order. The sum over T is the only
        # rounding step, and its order is pinned (sum_fixed_order), so two
        # programs that see bit-equal features emit bit-equal
        # probabilities.
        per_tree = jnp.sum(jnp.where(match, g.leaf_val[None], 0.0), axis=2)
        return sum_fixed_order(per_tree, axis=1)


def gemm_predict_proba(
    g: GemmEnsemble, x: jnp.ndarray, z_mode: str | None = None
) -> jnp.ndarray:
    """[B, F] → probability [B] (bagging mean over trees)."""
    return gemm_leaf_sum(g, x, z_mode) / g.n_trees


def predict_proba(
    params, x: jnp.ndarray, z_mode: str | None = None
) -> jnp.ndarray:
    """Unified forest scorer: dispatches on the ensemble form.

    The GEMM form is ~100× faster than the gather-based descent on TPU
    (measured on v5e: 3.2M vs 31k rows/s at B=32k, T=100, depth 8) because
    XLA lowers [B, T]-indexed table gathers to a slow serial path while the
    three contractions tile straight onto the MXU. Both are decision-exact
    vs sklearn on f32 inputs. ``z_mode`` selects the GEMM form's z
    arithmetic (the descent form has no contraction and ignores it).
    """
    if isinstance(params, GemmEnsemble):
        return gemm_predict_proba(params, x, z_mode)
    return ensemble_predict_proba(params, x)


def for_device(
    ens: TreeEnsemble, n_features: int, max_gemm_bytes: int = 256 * 1024 * 1024
) -> "TreeEnsemble | GemmEnsemble":
    """Pick the fastest exact device form for a compiled ensemble.

    GEMM inflates memory as O(T·N²) for the path matrix, which is fine for
    depth-bounded forests (the reference's production RF) but explodes for
    unbounded trees (the reference's DT-∞ experiment,
    ``model_training.ipynb · cell 50``) — those keep the descent form.
    """
    t, n = ens.feat.shape
    if 4 * t * n * n <= max_gemm_bytes:
        return to_gemm(ens, n_features)
    return ens


def synthetic_ensemble(
    n_trees: int = 4,
    max_depth: int = 3,
    n_features: int = 15,
    seed: int = 0,
) -> TreeEnsemble:
    """A shape-faithful ensemble with NO training dependency.

    Complete binary trees of exactly ``max_depth`` levels with random
    (but valid) feature indices, thresholds and leaf probabilities —
    structurally indistinguishable from an ``ensemble_from_sklearn``
    product, so anything that needs an ensemble's SHAPES and traced
    program (``tools/rtfdsverify``'s device-contract proofs, template
    tests, ``to_gemm``/``to_pallas`` padding math) can build one without
    sklearn or data. The probabilities are arbitrary: do not score real
    traffic with it.
    """
    rng = np.random.default_rng(seed)
    n = 2 ** (max_depth + 1) - 1  # complete binary tree node count
    n_internal = 2 ** max_depth - 1
    idx = np.arange(n, dtype=np.int32)
    is_leaf = idx >= n_internal
    feat = np.where(
        is_leaf[None, :], 0,
        rng.integers(0, n_features, size=(n_trees, n)),
    ).astype(np.int32)
    thresh = np.where(
        is_leaf[None, :], 0.0,
        rng.normal(size=(n_trees, n)),
    ).astype(np.float32)
    left = np.where(is_leaf, idx, idx * 2 + 1).astype(np.int32)
    right = np.where(is_leaf, idx, idx * 2 + 2).astype(np.int32)
    prob = rng.uniform(size=(n_trees, n)).astype(np.float32)
    return TreeEnsemble(
        feat=jnp.asarray(feat),
        thresh=jnp.asarray(ftz_safe_thresholds(thresh)),
        left=jnp.asarray(np.broadcast_to(left, (n_trees, n)).copy()),
        right=jnp.asarray(np.broadcast_to(right, (n_trees, n)).copy()),
        prob=jnp.asarray(prob),
        max_depth=max_depth,
    )


def fit_forest(
    x: np.ndarray,
    y: np.ndarray,
    n_trees: int = 100,
    max_depth: int = 8,
    seed: int = 0,
    kind: str = "forest",
) -> TreeEnsemble:
    """Host-side fit (sklearn, mirroring the reference's offline training)
    then compile to the TPU ensemble."""
    from sklearn.ensemble import RandomForestClassifier
    from sklearn.tree import DecisionTreeClassifier

    if kind == "tree":
        clf = DecisionTreeClassifier(max_depth=max_depth, random_state=seed)
    else:
        clf = RandomForestClassifier(
            n_estimators=n_trees, max_depth=max_depth, random_state=seed, n_jobs=-1
        )
    clf.fit(x, y)
    return ensemble_from_sklearn(clf, x.shape[1])
