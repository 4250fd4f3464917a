"""Shared Pallas block-size validation.

Every ``pallas_call`` wrapper in this package validates its block sizes
here so that (a) the clamp-then-check order is identical everywhere —
defaulted block sizes are first clamped to the array dim, *then* checked
for divisibility — and (b) error messages are uniform
(``block_x=B must divide X=D`` / ``block_x=B exceeds X=D``), so tests and
the static analyzer (:mod:`repro.analysis`) can match them.

The same constants and pure helpers back the analyzer's Pallas resource
rule: :func:`check_block_shape` re-checks divisibility on block shapes
recovered from a staged jaxpr, and :func:`estimate_vmem_bytes` estimates
the per-grid-step VMEM footprint against :data:`VMEM_BUDGET_BYTES`.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

#: Per-backend VMEM budget for one grid step's resident blocks, in bytes.
#: TPU cores have ~16 MiB of VMEM (see the Pallas TPU guide); the compiler
#: needs headroom for scratch/double-buffering, so the lint budget is half.
#: Non-TPU backends interpret the kernels, but are checked against the TPU
#: budget anyway — that is the point of linting on CPU in CI.
VMEM_BYTES = {"tpu": 16 * 2 ** 20}
VMEM_BUDGET_BYTES = {k: v // 2 for k, v in VMEM_BYTES.items()}

#: TPU vreg tiling of a VMEM buffer: its last axis pads to a multiple of
#: LANES; its second-to-last to SUBLANES rows of 32-bit words, i.e. 8 rows
#: for 32-bit types, 16 for 16-bit and 32 for 8-bit types.
LANES = 128
SUBLANES = 8


def validate_block(name: str, block: int, dim: int, dim_name: str,
                   clamp: bool = True) -> int:
    """Validate (and optionally clamp) one block size against its dim.

    With ``clamp=True`` (the defaulted-block-size convention) the block is
    first reduced to ``min(block, dim)``; with ``clamp=False`` an oversized
    block is an error (the explicit-block-size convention).  Either way the
    resulting block must divide the dim exactly — Pallas would silently pad
    otherwise, and padded tiles break the routed/packed layouts.

    Returns the validated (possibly clamped) block size.
    """
    if block < 1:
        raise ValueError(f"{name}={block} must be >= 1")
    if block > dim:
        if not clamp:
            raise ValueError(f"{name}={block} exceeds {dim_name}={dim}")
        block = dim
    if dim % block:
        raise ValueError(f"{name}={block} must divide {dim_name}={dim}")
    return block


def validate_blocks(spec: Sequence[Tuple[str, int, int, str]],
                    clamp: bool = True) -> Tuple[int, ...]:
    """Validate several ``(name, block, dim, dim_name)`` entries at once."""
    return tuple(validate_block(name, block, dim, dim_name, clamp=clamp)
                 for name, block, dim, dim_name in spec)


# ---------------------------------------------------------------------------
# Pure checkers shared with the static analyzer (no raising — they return
# problem strings so the analyzer can turn them into findings).
# ---------------------------------------------------------------------------

def check_block_shape(block_shape: Sequence, array_shape: Sequence[int],
                      ) -> List[str]:
    """Divisibility problems of one BlockSpec against its array shape.

    Non-integer block entries (squeezed/mapped grid dims) are skipped.
    """
    problems: List[str] = []
    if len(block_shape) != len(array_shape):
        return [f"block rank {len(block_shape)} != array rank "
                f"{len(array_shape)}"]
    for axis, (b, d) in enumerate(zip(block_shape, array_shape)):
        if not isinstance(b, (int, np.integer)):
            continue
        if b > d:
            problems.append(f"block dim {int(b)} exceeds array dim {d} "
                            f"(axis {axis})")
        elif d % b:
            problems.append(f"block dim {int(b)} does not divide array dim "
                            f"{d} (axis {axis})")
    return problems


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def block_bytes(block_shape: Sequence, dtype) -> int:
    """VMEM bytes of one block, tile padding included.

    Non-integer (squeezed/mapped) entries count as 1.  The last two axes
    pad to the vreg tile (see :data:`LANES`, :data:`SUBLANES`): a
    (640, 240, 4) f32 block occupies (640, 240 -> 248, 4 -> 128) words.
    """
    dims = [int(b) if isinstance(b, (int, np.integer)) else 1
            for b in block_shape]
    itemsize = np.dtype(dtype).itemsize
    if dims:
        dims[-1] = _round_up(dims[-1], LANES)
    if len(dims) > 1:
        dims[-2] = _round_up(dims[-2], SUBLANES * max(4 // itemsize, 1))
    return int(np.prod(dims, dtype=np.int64)) * itemsize


def estimate_vmem_bytes(blocks: Sequence[Tuple[Sequence, object]]) -> int:
    """Per-grid-step VMEM estimate: sum of (block_shape, dtype) buffers.

    One buffer per kernel operand/output; double-buffering and scratch are
    the compiler's business — the budget constant leaves headroom for them.
    """
    return sum(block_bytes(shape, dt) for shape, dt in blocks)


def vmem_budget(backend: Optional[str] = None) -> int:
    """VMEM lint budget for ``backend`` (default: the TPU budget).

    A backend without a known VMEM size is an error, not a default."""
    backend = backend or "tpu"
    if backend not in VMEM_BUDGET_BYTES:
        raise ValueError(f"no VMEM size known for backend {backend!r}")
    return VMEM_BUDGET_BYTES[backend]
