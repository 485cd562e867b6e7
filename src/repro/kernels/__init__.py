"""Pallas TPU kernels: generated fused PEs (the paper technique), flash
attention, selective scan, MXU matmul with PE epilogues.  They compile for
the TPU; on the ``cpu`` platform they run in interpret mode
(:func:`default_interpret`), where the tests validate them against the
ref.py oracles."""

from .ops import (attention, default_interpret, fused_pe_apply, matmul_fused,
                  selective_scan)
from .pe_fused import kernel_from_config, make_pe_kernel

__all__ = ["attention", "default_interpret", "fused_pe_apply",
           "matmul_fused", "selective_scan", "kernel_from_config",
           "make_pe_kernel"]
