"""Distributed byzantine-SGD layer, built on the core plan/apply API.

* ``trainer``   — stacked n×d trainer (`make_train_step`, `split_workers`,
  `inject_byzantine`);
* ``streaming`` — per-block streaming trainer (398B enabler, DESIGN.md §5);
* ``serving``   — batched prefill/decode (`generate`, `make_serve_step`) and
  the byzantine-tolerant replica ensemble (`make_robust_serve_step`);
* ``sharding``  — PartitionSpec heuristics for the production mesh.
"""
from repro.dist.trainer import (  # noqa: F401
    TrainerState,
    as_trainer_state,
    init_train_state,
    inject_byzantine,
    jit_train_step,
    make_train_step,
    replicate_on_mesh,
    split_workers,
)
from repro.dist import sharding  # noqa: F401
