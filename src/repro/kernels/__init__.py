"""Pallas TPU kernels for the complementary-sparsity compute hot-spots.

Kernels (each: <name>.py with pl.pallas_call + BlockSpec VMEM tiling,
``ops.py`` jit'd/differentiable wrappers, ``ref.py`` pure-jnp oracles):

* ``packed_matmul``     — matmul with in-VMEM CS decompression (MXU path).
* ``grouped_cs_matmul`` — shared-route grouped matmul (N× fewer MXU FLOPs).
* ``topk_gather``       — batched sparse-sparse contraction (K non-zeros
  only; (nG, B) grid keeps the packed tile VMEM-resident across the whole
  decode batch — one launch per layer per step).
* ``kwta_exact``        — exact k-WTA by radix select, no sort or scatter:
  what :func:`repro.core.kwta.kwta` runs on a TPU (the same formula runs
  in ``jnp`` elsewhere), chosen by backend with no flag.
* ``kwta_hist``         — histogram-threshold global k-WTA (paper Fig. 10).

Apart from ``kwta_exact``, layer code does not call these directly:
``packed_linear_apply`` routes through the executor flag
``SparsityConfig.use_pallas`` ('auto' = Pallas on TPU only, 'force' =
everywhere with interpret fallback off-TPU, 'off' = pure jnp) — see
:func:`repro.core.api.choose_executor`.  The serving entrypoint exposes
it as ``Engine(..., use_pallas=...)`` / ``--use-pallas``.
"""

from .block_validation import (check_block_shape, estimate_vmem_bytes,
                               validate_block, validate_blocks, vmem_budget)
from .grouped_cs_matmul import (grouped_cs_matmul, interleave_out,
                                permute_activations, slot_major_packed)
from .kwta_exact import kwta_exact_pallas
from .kwta_hist import kwta_hist_pallas
from .ops import (grouped_cs_matmul_op, kwta_exact_op, kwta_hist_op,
                  packed_matmul_op, topk_gather_op, topk_gather_support_op)
from .packed_matmul import packed_matmul, to_partition_major
from .registry import KernelCase, kernel_cases
from .topk_gather import topk_gather_matmul, topk_support

__all__ = [
    "grouped_cs_matmul", "interleave_out", "permute_activations",
    "slot_major_packed", "kwta_exact_pallas", "kwta_hist_pallas",
    "grouped_cs_matmul_op", "kwta_exact_op", "kwta_hist_op",
    "packed_matmul_op", "topk_gather_op", "topk_gather_support_op",
    "packed_matmul", "to_partition_major", "topk_gather_matmul",
    "topk_support", "KernelCase", "kernel_cases",
    "check_block_shape", "estimate_vmem_bytes", "validate_block",
    "validate_blocks", "vmem_budget",
]
