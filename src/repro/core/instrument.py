"""Trace-time instrumentation for the sparse execution paths.

The paper's Fig. 8a pipeline runs ONE Select (top-k / k-WTA) per sparse
layer; re-deriving the support downstream (e.g. ``cs_topk_matmul`` calling
``lax.top_k`` on an already k-sparse input) silently doubles the Select
cost.  Every Select call site in this repo goes through
:func:`counted_top_k`, so tests can trace a layer (``jax.make_jaxpr``) and
assert exactly one top_k was staged out per sparse layer:

    with count_selects() as c:
        jax.make_jaxpr(fn)(x)
    assert c.top_k == 1

A sort-free Select (exact k-WTA by radix select, :func:`repro.core.kwta.kwta`)
stages under :func:`staged_select` and ticks a count of its own,
``c.counts["threshold"]``, so a test can see which implementation each
site staged.

Counters tick at *trace* time — inside ``lax.scan`` bodies they count once
per traced superblock, and jit cache hits don't tick them (use
``jax.make_jaxpr`` or a fresh function to force a trace when asserting).

The authoritative check of the one-Select invariant is the static pass in
:mod:`repro.analysis`, which counts ``top_k``/``sort`` primitives in the
staged jaxpr itself and therefore sees *every* Select, including ones that
bypass :func:`counted_top_k`.  The counters here remain as a lightweight
trace-time probe.
"""

from __future__ import annotations

import contextlib
import threading
import warnings
from typing import Iterator

from jax import lax


class SelectCounter:
    """Per-``with``-block Select counts (see :func:`count_selects`)."""

    def __init__(self) -> None:
        self.counts = {"top_k": 0, "threshold": 0}

    @property
    def top_k(self) -> int:
        return self.counts["top_k"]

    def reset(self) -> None:
        for k in self.counts:
            self.counts[k] = 0


class _State(threading.local):
    def __init__(self) -> None:
        self.stack: list[SelectCounter] = []
        #: legacy process-global counter backing the deprecated
        #: ``topk_call_count``/``reset_topk_count`` API.
        self.legacy = SelectCounter()


_STATE = _State()


@contextlib.contextmanager
def count_selects() -> Iterator[SelectCounter]:
    """Count Select (top_k) call sites staged while the block is active.

    Scoped and re-entrant: each ``with`` block gets its own
    :class:`SelectCounter`, nested blocks all tick, and counters on other
    threads are untouched — concurrent tests can't corrupt each other's
    counts the way the old module-global counter could.
    """
    c = SelectCounter()
    _STATE.stack.append(c)
    try:
        yield c
    finally:
        _STATE.stack.remove(c)


@contextlib.contextmanager
def staged_select(kind: str) -> Iterator[None]:
    """Tick every active Select counter's ``kind`` count (trace-time) and
    stage the block under a ``select`` name scope, so the jaxpr-level
    Select-count rule (:mod:`repro.analysis`) can attribute what it stages
    to the enclosing layer scope."""
    import jax
    for c in _STATE.stack:
        c.counts[kind] += 1
    _STATE.legacy.counts[kind] += 1
    with jax.named_scope("select"):
        yield


def counted_top_k(x, k: int):
    """``lax.top_k`` that ticks every active Select counter (trace-time)."""
    with staged_select("top_k"):
        return lax.top_k(x, k)


def _warn_deprecated(name: str) -> None:
    warnings.warn(
        f"repro.core.instrument.{name} is deprecated: the process-global "
        "counter is not safe under concurrent tracing. Use "
        "`with count_selects() as c:` instead.",
        DeprecationWarning, stacklevel=3)


def topk_call_count() -> int:
    """Deprecated shim: global Select count since the last reset."""
    _warn_deprecated("topk_call_count")
    return _STATE.legacy.top_k


def reset_topk_count() -> None:
    """Deprecated shim: reset the global Select counter."""
    _warn_deprecated("reset_topk_count")
    _STATE.legacy.reset()
