"""Realized-sparsity telemetry: winner-support capture + path attribution.

The paper's throughput claim rides on the *realized* activation sparsity
at runtime, not the configured k/N (arXiv 2112.13896 §4; arXiv 2311.07625
for the activity-sparse decode regime).  The static linter
(:mod:`repro.analysis`) proves the staged program keeps the sparse-sparse
structure; this module measures what actually flows through it:

* **Support capture** — a trace-time collector that rides along the
  serving engine's *probed* decode step.  ``apply_kwta`` (and the bisect/
  hist datapaths, via an nnz reduction) report each layer's winner set to
  the active capture; :func:`drain_pending`/:func:`emit_stacked` thread
  those arrays through ``lax.scan`` in ``transformer.serve_step`` so the
  per-unit winner indices come back stacked ``(n_units, B, K)`` as extra
  jit outputs.  **When no capture is active every hook is a no-op and the
  staged jaxpr is bit-identical to the un-instrumented one** — the
  telemetry-off path stages nothing (asserted by ``tests/test_obs.py``
  and re-proven by ``repro.analysis`` in CI).
* **SparsityStats** — host-side accumulation over probed steps: realized
  k/N per layer (winners with non-zero value / feature dim; for the
  >=-K threshold impls, the measured keep count), and cross-step winner
  overlap per layer (|support_t ∩ support_{t-1}| / K per slot, reset on
  request admission).
* **DispatchStats** — trace-time execution-path attribution fed by the
  observer hook in :mod:`repro.core.api`: how many CS layer sites each
  path (topk / hadamard / dense) and backend (pallas / interpret / jnp)
  staged.  Counts only; time per kernel comes from a device trace.

No module here imports :mod:`repro.core` or :mod:`repro.models` — the
hooks point the other way, so the capture can be active while those
modules trace.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

__all__ = ["SupportCapture", "capture_supports", "observe_site",
           "observe_support", "observe_activation", "drain_pending",
           "emit_stacked", "capture_active", "SparsityStats",
           "DispatchStats"]


# ---------------------------------------------------------------------------
# Trace-time support capture
# ---------------------------------------------------------------------------

class _Tls(threading.local):
    def __init__(self):
        self.capture: Optional["SupportCapture"] = None
        self.sites: List[str] = []


_TLS = _Tls()


class SupportCapture:
    """One probed trace's collected winner sets.

    ``pending`` holds entries observed since the last :func:`drain_pending`
    (i.e. within the current scan-body trace); ``entries``/``meta`` hold
    the post-scan stacked arrays keyed by layer label.
    """

    def __init__(self):
        # [(label, d, kind, (arrays...))] — arrays are jax tracers
        self.pending: List[Tuple[str, int, str, tuple]] = []
        self._drained_meta: List[Tuple[str, int, str]] = []
        self.entries: Dict[str, tuple] = {}
        self.meta: Dict[str, Dict] = {}

    def _label(self, base: str) -> str:
        label = ".".join(_TLS.sites + [base]) if _TLS.sites else base
        k, out = 2, label
        seen = {l for (l, _, _, _) in self.pending} | set(self.entries)
        while out in seen:
            out = f"{label}#{k}"
            k += 1
        return out

    def add(self, base: str, d: int, kind: str, arrays: tuple) -> None:
        self.pending.append((self._label(base), d, kind, arrays))

    def take_arrays(self) -> Dict[str, tuple]:
        """Jit-output pytree: ``{label: (arrays...)}`` (arrays only; the
        static meta travels via :attr:`meta` on the Python side)."""
        return dict(self.entries)


def capture_active() -> bool:
    return _TLS.capture is not None


@contextlib.contextmanager
def capture_supports() -> Iterator[SupportCapture]:
    """Activate a :class:`SupportCapture` for the current thread.

    Wrap the *trace* of the function to probe (the serving engine wraps
    the body of its probed decode-step jit).  Nested captures shadow the
    outer one.
    """
    prev = _TLS.capture
    cap = SupportCapture()
    _TLS.capture = cap
    try:
        yield cap
    finally:
        _TLS.capture = prev


@contextlib.contextmanager
def observe_site(label: str) -> Iterator[None]:
    """Push a site label (e.g. ``b0``, ``ffn``) onto the capture's label
    path.  Cheap enough to wrap every block at trace time unconditionally."""
    _TLS.sites.append(label)
    try:
        yield
    finally:
        _TLS.sites.pop()


def observe_support(vals, idx, d: int, site: str = "kwta") -> None:
    """Report an exact-top-k winner set ``(vals (..., K), idx (..., K))``
    over a ``d``-wide axis.  No-op without an active capture."""
    cap = _TLS.capture
    if cap is None:
        return
    cap.add(site, d, "support", (vals, idx))


def observe_activation(y, site: str = "kwta") -> None:
    """Report a thresholded k-sparse activation with no index form (the
    hist/bisect >=-K datapaths): stages a per-row nnz reduction — only
    when a capture is active, so the un-probed path stays untouched."""
    cap = _TLS.capture
    if cap is None:
        return
    import jax.numpy as jnp
    nnz = jnp.sum((y != 0), axis=-1).astype(jnp.int32)
    cap.add(site, y.shape[-1], "nnz", (nnz,))


def drain_pending() -> tuple:
    """Pull the entries observed inside the current scan-body trace, as a
    tuple suitable for a ``lax.scan`` body output (stacked over the scan
    axis).  Returns ``()`` when no capture is active — the scan output
    pytree gains no leaves and the staged jaxpr is unchanged."""
    cap = _TLS.capture
    if cap is None or not cap.pending:
        return ()
    cap._drained_meta = [(l, d, k) for (l, d, k, _) in cap.pending]
    out = tuple(arrays for (_, _, _, arrays) in cap.pending)
    cap.pending = []
    return out


def emit_stacked(aux: tuple) -> None:
    """Attach the scan-stacked drain outputs back to the capture, keyed by
    the labels recorded at drain time.  No-op when inactive or empty."""
    cap = _TLS.capture
    if cap is None or not aux:
        return
    for (label, d, kind), arrays in zip(cap._drained_meta, aux):
        cap.entries[label] = tuple(arrays)
        cap.meta[label] = {"d": d, "kind": kind}


# ---------------------------------------------------------------------------
# Host-side realized-sparsity accumulation
# ---------------------------------------------------------------------------

class SparsityStats:
    """Accumulates probed-step winner sets into per-layer statistics.

    Layers are keyed ``{label}.u{unit}`` (scan-stacked captures carry a
    leading unit axis).  Per layer: mean realized k/N (non-zero winners /
    feature dim) and mean cross-step winner overlap (support kind only).
    Overlap for a slot row is suppressed until the row has two probed
    steps from the *same* request (:meth:`reset_row` on admission).
    """

    def __init__(self, registry=None):
        from .metrics import NULL_REGISTRY
        self._reg = registry if registry is not None else NULL_REGISTRY
        self._prev_idx: Dict[str, np.ndarray] = {}
        self._row_valid: Optional[np.ndarray] = None
        self._acc: Dict[str, Dict[str, float]] = {}
        self.probes = 0

    def reset_row(self, row: int) -> None:
        """A new request took slot ``row``: don't bridge overlap across it."""
        if self._row_valid is not None and row < self._row_valid.shape[0]:
            self._row_valid[row] = False

    def _layer(self, name: str, d: int, k: int) -> Dict[str, float]:
        a = self._acc.get(name)
        if a is None:
            a = self._acc[name] = {"d": d, "k": k, "realized_sum": 0.0,
                                   "realized_n": 0, "overlap_sum": 0.0,
                                   "overlap_n": 0}
        return a

    def update(self, arrays: Dict[str, tuple], meta: Dict[str, Dict],
               active_rows: Sequence[int]) -> None:
        """Fold one probed step's captured arrays into the accumulators.

        ``arrays``/``meta`` come from the probed jit's aux output and the
        capture's meta dict; ``active_rows`` are the slot rows holding
        live requests this step (idle rows carry stale activations).
        """
        if not arrays or not active_rows:
            return
        self.probes += 1
        active = np.asarray(sorted(active_rows), np.int32)
        realized_fracs, overlap_means = [], []
        for label in sorted(arrays):
            m = meta[label]
            d, kind = int(m["d"]), m["kind"]
            if kind == "support":
                vals = np.asarray(arrays[label][0])
                idx = np.asarray(arrays[label][1])
                if vals.ndim == 2:          # eager capture: no unit axis
                    vals, idx = vals[None], idx[None]
                # collapse any middle dims (decode carries S=1: (U,B,1,K))
                u, k = vals.shape[0], vals.shape[-1]
                vals = vals.reshape(u, -1, k)
                idx = idx.reshape(u, -1, k)
                u, b, k = idx.shape
                if self._row_valid is None or self._row_valid.shape[0] != b:
                    self._row_valid = np.zeros((b,), bool)
                realized = (vals != 0).sum(-1)                    # (U, B)
                prev = self._prev_idx.get(label)
                overlaps = None
                if prev is not None and prev.shape == idx.shape:
                    # row-offset trick: shift each (unit, row) into its own
                    # index space so one np.isin covers the whole batch
                    off = (np.arange(u * b, dtype=np.int64)
                           .reshape(u, b, 1)) * d
                    cur = idx.astype(np.int64) + off
                    old = prev.astype(np.int64) + off
                    hit = np.isin(cur.ravel(), old.ravel())
                    overlaps = hit.reshape(u, b, k).sum(-1) / k   # (U, B)
                self._prev_idx[label] = idx
                for ui in range(u):
                    a = self._layer(f"{label}.u{ui}", d, k)
                    r = realized[ui, active] / d
                    a["realized_sum"] += float(r.sum())
                    a["realized_n"] += int(active.size)
                    realized_fracs.append(float(r.mean()))
                    if overlaps is not None:
                        ok = active[self._row_valid[active]]
                        if ok.size:
                            o = overlaps[ui, ok]
                            a["overlap_sum"] += float(o.sum())
                            a["overlap_n"] += int(ok.size)
                            overlap_means.append(float(o.mean()))
            elif kind == "nnz":
                nnz = np.asarray(arrays[label][0])
                if nnz.ndim == 1:
                    nnz = nnz[None]
                nnz = nnz.reshape(nnz.shape[0], -1)  # (U, B*S), decode S=1
                u, b = nnz.shape
                for ui in range(u):
                    a = self._layer(f"{label}.u{ui}", d, -1)
                    r = nnz[ui, active] / d
                    a["realized_sum"] += float(r.sum())
                    a["realized_n"] += int(active.size)
                    realized_fracs.append(float(r.mean()))
        if self._row_valid is not None:
            self._row_valid[:] = False
            self._row_valid[active] = True
        if realized_fracs:
            self._reg.gauge("sparsity.realized_k_frac").set(
                float(np.mean(realized_fracs)))
        if overlap_means:
            self._reg.gauge("sparsity.winner_overlap").set(
                float(np.mean(overlap_means)))
        self._reg.counter("sparsity.probe_steps").inc()

    def summary(self) -> Dict[str, Dict]:
        """Per-layer means: ``{layer: {d, k, realized_k_frac,
        winner_overlap, samples}}`` (overlap absent for nnz layers)."""
        out: Dict[str, Dict] = {}
        for name, a in sorted(self._acc.items()):
            e = {"d": int(a["d"]), "samples": int(a["realized_n"])}
            if a["k"] > 0:
                e["k"] = int(a["k"])
                e["configured_k_frac"] = round(a["k"] / a["d"], 6)
            if a["realized_n"]:
                e["realized_k_frac"] = round(
                    a["realized_sum"] / a["realized_n"], 6)
            if a["overlap_n"]:
                e["winner_overlap"] = round(
                    a["overlap_sum"] / a["overlap_n"], 6)
            out[name] = e
        return out


# ---------------------------------------------------------------------------
# Execution-path attribution (trace-time, fed by repro.core.api hook)
# ---------------------------------------------------------------------------

class DispatchStats:
    """Records the execution-path decision of every CS layer staged while
    unsealed (the engine seals after the first decode-step trace, so the
    site list describes exactly one staged decode step; a ``lax.scan``
    body counts once, however many units it runs)."""

    def __init__(self):
        self.sites: List[Dict] = []
        self._sealed = False
        self._lock = threading.Lock()

    def on_event(self, ev: Dict) -> None:
        with self._lock:
            if not self._sealed:
                self.sites.append(dict(ev))

    def seal(self) -> None:
        with self._lock:
            self._sealed = True

    @property
    def sealed(self) -> bool:
        return self._sealed

    def summary(self) -> Dict[str, int]:
        """Staged sites per path and backend, e.g. ``{"topk[pallas]":
        32}``."""
        agg: Dict[str, int] = {}
        for ev in self.sites:
            backend = ("pallas-interpret" if ev.get("interpret")
                       else "pallas") if ev.get("pallas") else "jnp"
            key = f"{ev['path']}[{backend}]"
            agg[key] = agg.get(key, 0) + 1
        return agg
