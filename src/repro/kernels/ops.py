"""Jit'd public wrappers for the Pallas kernels (interpret-mode default on
CPU; pass interpret=False on real TPU)."""
from repro.kernels.flash_attention import flash_attention  # noqa: F401
from repro.kernels.rbf import rbf_kernel_matrix  # noqa: F401
from repro.kernels.smo_step import fused_smo_step  # noqa: F401
