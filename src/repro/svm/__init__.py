"""SVM substrate: SMO solver, kernel functions, classifier API.

The SVM stack follows LIBSVM's precision policy (``repro.svm.precision``):
kernel values in float32, solver state in float64. float64 state needs
x64, which we enable here; f32 matmuls run at full f32 precision (a TPU
otherwise rounds their operands to bfloat16, which moves an RBF kernel
value by up to a few percent). The LM model zoo is dtype-explicit
everywhere, so it is unaffected.
"""
import jax

jax.config.update("jax_enable_x64", True)
jax.config.update("jax_default_matmul_precision", "highest")

from repro.svm.kernels import rbf_kernel, linear_kernel, kernel_matrix  # noqa: E402,F401
from repro.svm.engine import (  # noqa: E402,F401
    DenseKernel, EngineState, FusedRBF, OnDemandRBF, PallasRBF, ShardedRBF)
from repro.svm.sources import KernelSpec, SourceCache  # noqa: E402,F401
from repro.svm.shrink import (  # noqa: E402,F401
    LaneShrink, bucket_cap, possible_caps, seed_active_mask, solve_shrunk)
from repro.svm.scheduler import LanePool  # noqa: E402,F401
from repro.svm.smo import (  # noqa: E402,F401
    SMOResult, smo_solve, init_f, dual_objective)
from repro.svm.svc import (  # noqa: E402,F401
    SVC, decision_function, predict, accuracy, bias_from_solution)
