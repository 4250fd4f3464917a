"""Registry of the package's Pallas kernels for the kernel-body verifier.

Each entry declares how to *stage* one shipped kernel wrapper at a given
shape configuration (abstract tracing only — nothing runs), plus the
value-range **provenance** of its index-carrying operands.  The verifier
(:mod:`repro.analysis.kernel_rules`) sweeps every case and proves the
body's Ref accesses in-bounds, its cross-grid-step writes race-free, its
padded loads masked, and its scratch within the VMEM budget.

The provenance declarations are the verifier's trust root: they encode
facts about how the *wrappers'* callers construct the operands, which
the kernel body alone cannot know.  Each registration carries a comment
saying why the range holds; if a caller ever violates it, the proof is
vacuous — keep the declarations next to the code that guarantees them.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, List

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class KernelCase:
    """One (kernel, shape config) staging recipe.

    ``trace()`` returns the ClosedJaxpr of the wrapper applied to
    abstract operands at this configuration."""

    kernel: str          # wrapper name: topk_gather, grouped_cs_matmul, ...
    label: str           # e.g. "topk_gather[b4 k16 p32 g8 n4 bg8]"
    trace: Callable[[], object]

    def __repr__(self):  # pragma: no cover - debugging nicety
        return f"KernelCase({self.label})"


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))


def _trace(fn, *args, **static):
    import functools
    return jax.make_jaxpr(functools.partial(fn, **static))(*args)


# ---------------------------------------------------------------------------
# Shape sweeps.  Each tuple is one configuration the CI sweep must prove
# clean; they bracket the regimes the serving/train paths actually use
# (single-tile grids, multi-k accumulation grids, batched decode grids).
# ---------------------------------------------------------------------------

#: topk_gather_matmul: (b, k_nnz, p, g, n, block_g); a group tile spans
#: block_g*n lanes, a multiple of 128 unless it covers all g groups.
TOPK_GATHER_SWEEP = (
    (4, 16, 32, 8, 4, 8),       # decode batch, single group tile
    (8, 32, 64, 64, 4, 32),     # grid (2, 8): group-tiled, batch innermost
    (2, 8, 16, 4, 4, 4),        # tiny shapes
    (4, 320, 640, 240, 4, 240),  # smollm-360m FFN down-projection, decode
    (7, 320, 640, 240, 4, 240),  # ... at the largest topk-path batch
)

#: grouped_cs_matmul: (n, b, p, g, block_b, block_p, block_g)
GROUPED_CS_SWEEP = (
    (4, 8, 16, 8, 128, 256, 256),    # defaults clamp to dims: grid (4,1,1,1)
    (4, 16, 64, 32, 8, 16, 16),      # multi-k grid: nk = 4 accumulation steps
    (2, 128, 256, 128, 64, 64, 64),  # serving-scale tiles, nk = 4
)

#: packed_matmul: (b, p, g, n, block_b, block_p, block_g)
PACKED_MATMUL_SWEEP = (
    (8, 8, 8, 4, 128, 64, 64),       # defaults clamp: single grid step
    (16, 32, 32, 4, 8, 8, 16),       # nk = 4 accumulation steps
    (128, 64, 64, 8, 64, 32, 32),    # serving-scale, nk = 2
)

#: kwta_hist_pallas: (b, d, k, block_b)
KWTA_HIST_SWEEP = (
    (8, 64, 8, 8),
    (16, 128, 16, 4),       # batch-tiled grid (4,)
)


#: kwta_exact_pallas: (g, d, n, k) — the GSC network's three k-WTA sites
#: at batch n = 1024: conv1's and conv2's channels (H*W groups of 64) and
#: the linear layer.
KWTA_EXACT_SWEEP = (
    (784, 64, 1024, 8),     # conv1, 28x28
    (100, 64, 1024, 8),     # conv2, 10x10
    (1, 1504, 1024, 180),   # linear
)


def kernel_cases() -> List[KernelCase]:
    """Every shipped kernel × shape configuration, as staging recipes."""
    from .grouped_cs_matmul import grouped_cs_matmul
    from .kwta_exact import kwta_exact_pallas
    from .kwta_hist import kwta_hist_pallas
    from .packed_matmul import packed_matmul
    from .topk_gather import topk_gather_matmul

    cases: List[KernelCase] = []

    for b, k, p, g, n, bg in TOPK_GATHER_SWEEP:
        cases.append(KernelCase(
            "topk_gather",
            f"topk_gather[b{b} k{k} p{p} g{g} n{n} bg{bg}]",
            lambda b=b, k=k, p=p, g=g, n=n, bg=bg: _trace(
                topk_gather_matmul,
                _sds((b, k), jnp.float32), _sds((b, k), jnp.int32),
                _sds((b, k), jnp.int32), _sds((p, g, n), jnp.bfloat16),
                _sds((p, g, n), jnp.int8), block_g=bg)))

    for n, b, p, g, bb, bp, bg in GROUPED_CS_SWEEP:
        cases.append(KernelCase(
            "grouped_cs_matmul",
            f"grouped_cs_matmul[n{n} b{b} p{p} g{g} bb{bb} bp{bp} bg{bg}]",
            lambda n=n, b=b, p=p, g=g, bb=bb, bp=bp, bg=bg: _trace(
                grouped_cs_matmul,
                _sds((n, b, p), jnp.float32), _sds((n, p, g), jnp.float32),
                block_b=bb, block_p=bp, block_g=bg)))

    for b, p, g, n, bb, bp, bg in PACKED_MATMUL_SWEEP:
        cases.append(KernelCase(
            "packed_matmul",
            f"packed_matmul[b{b} p{p} g{g} n{n} bb{bb} bp{bp} bg{bg}]",
            lambda b=b, p=p, g=g, n=n, bb=bb, bp=bp, bg=bg: _trace(
                packed_matmul,
                _sds((b, p * n), jnp.float32), _sds((p, g, n), jnp.float32),
                _sds((p, g, n), jnp.int8),
                block_b=bb, block_p=bp, block_g=bg)))

    for b, d, k, bb in KWTA_HIST_SWEEP:
        cases.append(KernelCase(
            "kwta_hist",
            f"kwta_hist[b{b} d{d} k{k} bb{bb}]",
            lambda b=b, d=d, k=k, bb=bb: _trace(
                kwta_hist_pallas, _sds((b, d), jnp.float32),
                k=k, block_b=bb)))

    for g, d, n, k in KWTA_EXACT_SWEEP:
        cases.append(KernelCase(
            "kwta_exact",
            f"kwta_exact[g{g} d{d} n{n} k{k}]",
            lambda g=g, d=d, n=n, k=k: _trace(
                kwta_exact_pallas, _sds((g, d, n), jnp.float32), k=k)))

    return cases


# ---------------------------------------------------------------------------
# Value-range provenance (trust root — see module docstring).
# ---------------------------------------------------------------------------

_provenance_registered = False


def ensure_provenance() -> None:
    """Idempotently register the kernels' value-range declarations.

    Called by the verifier on first use (not at import time — the
    registry and the verifier import each other's packages, so eager
    registration would be a circular import)."""
    global _provenance_registered
    if _provenance_registered:
        return
    _provenance_registered = True

    from repro.analysis.intervals import Interval
    from repro.analysis.kernel_rules import register_value_ranges

    def topk_gather_ranges(refs):
        # topk_support computes p_idx = sel // n from counted_top_k over
        # the flat [0, P*N) activation index space, so p_idx ∈ [0, P) by
        # construction.  The packed operand (position 3) is block-resident
        # along its full partition dim, so P is read off its block shape.
        # s_off is only compared against routes, never used as an index.
        return {1: Interval(0, refs[3].block_shape[0] - 1)}   # pidx_ref

    register_value_ranges("_topk_gather_kernel", topk_gather_ranges)
    # grouped_cs / packed_matmul / kwta_hist / kwta_exact index only with
    # program_id affine forms and static slices — no declared ranges needed.
