"""The paper's primary contribution: alpha-seeded SVM k-fold cross-validation.

Wen et al., AAAI 2017 — three seeding algorithms (ATO, MIR, SIR) that reuse
fold h's dual solution to warm-start fold h+1, plus the two prior
leave-one-out baselines (AVG, TOP) and the cold-start reference.
"""
from repro.core.seeding import (  # noqa: F401
    cold_seed, mir_seed, sir_seed, ato_seed, ato_seed_ref,
    avg_seed_loo, top_seed_loo, water_fill, repair_equality, SEEDERS,
)
from repro.core.study import (  # noqa: F401
    EvalSpec, LaneSpec, LaneStat, Plan, StudyCheckpoint, StudyResult,
    run_plan)
from repro.core.cv import run_cv, run_cv_batched, run_loo, CVReport, FoldStat  # noqa: F401
from repro.core.grid import run_grid, GridCell, GridReport  # noqa: F401
