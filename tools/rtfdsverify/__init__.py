"""rtfdsverify — jaxpr-level device-contract verifier for the rtfds
serving loop.

``tools/rtfdslint`` proves source-level invariants with pure ``ast``;
this package goes one level down, to the **traced program**: it builds
weightless template engines (synthetic shape-faithful models, CPU-only
jax, ``JAX_PLATFORMS=cpu``), loads each engine's **dispatch signature
inventory** (:meth:`ScoringEngine.dispatch_inventory` — the single
enumeration ``precompile()`` also compiles, so coverage proof and
warmup can never drift) and abstract-interprets every signature's
jitted step with ``jax.jit(...).trace`` / jaxpr inspection — no device
step ever executes, no weights are needed. Per signature it proves:

* **aot-coverage** — every runtime-reachable dispatch key is in the
  inventory and traces to a lowerable program, so a mid-stream XLA
  recompile is impossible by construction, not just counted at runtime
  (``rtfds_xla_recompiles_total`` stays the backstop);
* **zmode-exactness** — the PR-9 arithmetic-exactness contract as a
  checked theorem: integer z arithmetic survives in the int8 path,
  every float contraction (the decision projection) stays f32 pinned
  to HIGHEST, and no
  laundered downcast (f32→bf16/f16) enters the scoring program;
* **donation-safety** — the nan-guard's donation-off dance and the
  donate-only-the-feature-state rule, cross-checked against what the
  jit actually declares and whether every donated buffer can alias an
  output;
* **pallas-admission** — ``ops/pallas_forest.admit_block`` (the SAME
  predicate the engine's trace-time gate uses): VMEM block budget and
  MXU tile alignment hold statically for every signature with
  ``use_pallas`` reachable, and the traced program agrees with the
  verdict (a pallas_call is present iff admitted).

Findings report through the rtfdslint chassis (same P0/P1/P2
severities, ``--json`` schema, fingerprint baseline with required
reasons). Semantic findings have no single source line to pragma, so
the baseline (``tools/rtfdsverify/baseline.json``) is the suppression
channel.

Entry points:

* ``rtfds verify-device`` (CLI subcommand) / ``make verify-static``
* ``PYTHONPATH=tools python -m rtfdsverify`` from a checkout
* :func:`run_verify` for in-process use (the tier-1 gate test).
"""

from __future__ import annotations

import os
import sys

# rtfdsverify reuses the rtfdslint chassis (Finding/Baseline/severities);
# both live side by side under tools/, so a bare `import rtfdsverify`
# from a checkout must be able to find its sibling.
_TOOLS_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _TOOLS_DIR not in sys.path:
    sys.path.insert(0, _TOOLS_DIR)
_REPO_ROOT = os.path.dirname(_TOOLS_DIR)
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

from .runner import VerifyResult, run_verify  # noqa: E402,F401
from .checks import all_checks  # noqa: E402,F401

__version__ = "1.0.0"
