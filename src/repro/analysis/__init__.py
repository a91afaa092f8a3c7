"""Static analysis for trace discipline: AST lint + jaxpr contract audit.

Camel's measurements are only meaningful if the measured (energy,
latency) pair reflects the model and the hardware knobs — not accidental
host round-trips, silent retraces, or dtype upcasts.  This package is
the machine-checked version of that discipline, run in CI via::

    python -m repro.analysis --check

Two stages:

* **Stage 1 — AST lint** (`repro.analysis.lint`): a visitor framework
  over the whole ``src/repro`` tree with JAX-specific rules (R001-R005,
  see `repro.analysis.rules`).  A call graph built within the package
  propagates "jit-reachable" through helper calls, so a ``.item()``
  three frames below a ``lax.fori_loop`` body is still caught.
  Suppressions are explicit: ``# analysis: ignore[R001] reason`` — and
  an undocumented suppression (no reason) is itself a violation (R000).

* **Stage 2 — jaxpr contract audit** (`repro.analysis.jaxpr_audit`):
  traces every registered model family's ``prefill``/``decode_step``
  and the engine's slot-pool decode loop on tiny shapes and asserts
  machine-readable contracts — zero host callbacks, no float64, fp32
  softmax/logit accumulation, per-entry-point primitive-count budgets
  (``analysis_budgets.json``, diffed not just thresholded), and a
  retrace audit that fails when the jit cache grows on any axis that is
  not documented as shape-relevant (prompt buckets, batch arms).

Findings are emitted as JSON + human tables; the checked-in zero-entry
``baseline.json`` means new violations fail CI while grandfathering is
explicit and reviewable.  See docs/ANALYSIS.md for the rule catalogue.
"""

from repro.analysis.findings import (Finding, Report, load_baseline,
                                     render_findings)
from repro.analysis.lint import PackageIndex, run_lint
from repro.analysis.jaxpr_audit import run_audit

__all__ = ["Finding", "Report", "PackageIndex", "load_baseline",
           "render_findings", "run_lint", "run_audit"]
