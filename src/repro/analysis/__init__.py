"""Static contract verification for the repro codebase (DESIGN.md §12).

Three passes, none of which runs device code:

* :mod:`repro.analysis.lint` — AST lint with repo-specific rules
  (R001–R005): import-time device work, Python branches on tracers,
  registry spec strings, TrainerState indexing, jit static-argument
  hygiene (the PR-2 ``interpret``-baked-at-trace-time bug class).
* :mod:`repro.analysis.jaxpr_audit` — traces the aggregation paths and
  walks the jaxprs to *prove* the sharding contracts (C201–C205): no
  full (n, d) all-gather inside the apply shard body, the §9 decode
  invariant, the §10 tp-reshape seam, and single-compile trace caching.
* :mod:`repro.analysis.vmem` — static per-tile VMEM/HBM-traffic
  estimates for the Pallas kernels, cross-checked against the
  ``autotune_d_tile`` budget and the BENCH_agg_time.json traffic.

``repro.launch.analyze`` runs all three and writes the ``analysis.v1``
report (ANALYSIS.json); ``--strict`` makes any violation fatal, which is
how CI gates every kernel/sharding PR.
"""
from repro.analysis.lint import (  # noqa: F401
    Violation, lint_paths, lint_source)

__all__ = ["Violation", "lint_paths", "lint_source"]
