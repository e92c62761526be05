"""Quickstart: alpha-seeded 10-fold SVM cross-validation in 20 lines.

    PYTHONPATH=src python examples/quickstart.py
"""
from repro.compile_cache import enable_compile_cache
from repro.core.cv import run_cv
from repro.data.svm_suite import make_dataset

enable_compile_cache()

ds = make_dataset("madelon", n_override=600)
print(f"dataset={ds.name} n={ds.n} d={ds.X.shape[1]} C={ds.C} gamma={ds.gamma}")

run_cv(ds, k=10, method="cold"), run_cv(ds, k=10, method="sir")  # jit warmup
cold = run_cv(ds, k=10, method="cold")   # the LibSVM-style baseline
sir = run_cv(ds, k=10, method="sir")     # the paper's best seeder

print("\n          iterations   init(s)  solve(s)  accuracy")
for rep in (cold, sir):
    print(f"{rep.method:>6}    {rep.total_iterations:>10}   "
          f"{rep.total_init_time:7.3f}  {rep.total_solve_time:8.3f}  "
          f"{rep.accuracy:.4f}")
speedup = cold.total_solve_time / max(sir.total_init_time
                                      + sir.total_solve_time, 1e-9)
print(f"\nSIR is {speedup:.1f}x faster than cold-start CV, "
      f"identical accuracy = {sir.accuracy == cold.accuracy}")
