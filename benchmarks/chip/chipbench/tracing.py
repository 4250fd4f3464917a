"""Reduce a profiler trace to what the per-layer metrics read.

The trace (``jax.profiler``, an ``.xplane.pb``) holds one plane per
device, whose op events say when an operation ran on the chip, and host
planes whose thread lines hold the spans the harness annotated
(``TraceAnnotation``).  From them:

* the window: from the first annotated span's start to the last one's
  end;
* busy time: the union of the device's op intervals inside the window
  (averaged over the devices), and the idle gaps between them, each
  labelled with the innermost annotated span the host was in at the
  gap's middle;
* device time and run count per program (a module of the trace), under
  the labels the driver gives by a part of the module's name, or by the
  annotated span that launched it;
* device time and call count per kernel, found by name among the ops.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

#: Names of the device planes in a trace.
DEVICE_PLANE = "/device:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

Interval = Tuple[int, int]


def profile_options():
    """Trace the device and the host's annotations, not every Python
    call (the Python tracer slows the host it measures)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def clip(intervals: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    """The stretches of [lo, hi) that ``busy`` (sorted, disjoint) leaves
    free."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


@dataclasses.dataclass
class Span:
    name: str
    start: int
    end: int


class SpanIndex:
    """Annotated host spans, queried for the innermost one at a time."""

    def __init__(self, spans: List[Span]):
        self.spans = sorted(spans, key=lambda s: s.start)
        self.starts = [s.start for s in self.spans]

    #: How far back from ``t`` to look for a span that holds it.
    LOOKBACK = 256

    def at(self, t: int) -> Optional[str]:
        """The innermost span holding ``t``: on one thread spans nest, so
        it is the latest-starting one that has not ended."""
        i = bisect.bisect_right(self.starts, t)
        for s in reversed(self.spans[max(0, i - self.LOOKBACK):i]):
            if s.end > t:
                return s.name
        return None


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    ops: Dict[str, Tuple[int, float]]          # op name -> (count, s)
    #: label -> module name -> (runs, s): the modules enqueued under a label
    programs: Dict[str, Dict[str, Tuple[int, float]]]
    idle: List[Tuple[str, float]]              # (host span, s), longest 1st

    def idle_pct(self) -> float:
        return 100.0 * max(0.0, 1.0 - self.busy_s / self.window_s)

    def program_ms(self, label: str) -> Optional[float]:
        """Device milliseconds per run of the module that took the most
        device time under ``label`` (small programs enqueued alongside,
        such as a scalar's conversion, are left out)."""
        mods = self.programs.get(label)
        if not mods:
            return None
        runs, seconds = max(mods.values(), key=lambda rs: rs[1])
        return 1e3 * seconds / runs

    def kernel(self, name: str) -> Tuple[int, float]:
        """(calls, device seconds) of the ops whose name holds ``name``."""
        hits = [v for k, v in self.ops.items() if name in k]
        return (sum(c for c, _ in hits), sum(s for _, s in hits))

    def breakdown(self) -> Dict[str, List]:
        top = sorted(self.ops.items(), key=lambda kv: -kv[1][1])[:10]
        return {"device_ops": [[k, v[1]] for k, v in top],
                "idle_gaps": [[k, s] for k, s in self.idle[:10]]}


def _stats(event) -> Dict:
    return dict(event.stats)


def op_name(name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


def _leaves(events) -> List:
    """Op events that hold no other op event (a loop's op spans the ops
    of its body; counting both would count the body twice)."""
    evs = sorted(events, key=lambda e: (int(e.start_ns), -int(e.end_ns)))
    out = []
    for i, ev in enumerate(evs):
        nxt = evs[i + 1] if i + 1 < len(evs) else None
        if nxt is None or int(nxt.start_ns) >= int(ev.end_ns):
            out.append(ev)
    return out


def reduce_profile(pd, programs: Dict[str, str],
                   span_names: Optional[Iterable[str]] = None) -> Reduced:
    """Reduce a ``jax.profiler.ProfileData``.

    ``programs`` maps a part of a module's name, or the name of the
    annotated span in which the host enqueued it, to a label;
    ``span_names`` are the host events that count as annotated spans
    (default: every event on a host thread line).  The device's clock is
    shifted so that no program starts before the host enqueued it."""
    names = set(span_names) if span_names is not None else None
    spans: List[Span] = []
    enqueued: Dict[int, int] = {}
    device_planes = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE):
            device_planes.append(plane)
            continue
        for line in plane.lines:
            for ev in line.events:
                if names is None or ev.name in names:
                    spans.append(Span(ev.name, int(ev.start_ns),
                                      int(ev.end_ns)))
                run_id = _stats(ev).get("run_id")
                if run_id is not None:
                    t = int(ev.start_ns)
                    enqueued[int(run_id)] = min(t, enqueued.get(int(run_id),
                                                                t))
    if not spans:
        raise ValueError("the trace holds no annotated host span")
    index = SpanIndex(spans)
    lo = min(s.start for s in spans)
    hi = max(s.end for s in spans)

    modules = [ev for plane in device_planes for line in plane.lines
               if line.name == MODULES_LINE for ev in line.events]
    shift = 0
    for ev in modules:
        run_id = _stats(ev).get("run_id")
        if run_id is not None and int(run_id) in enqueued:
            shift = max(shift, enqueued[int(run_id)] - int(ev.start_ns))

    ops: Dict[str, List] = collections.defaultdict(lambda: [0, 0.0])
    progs: Dict[Tuple[str, str], List] = collections.defaultdict(
        lambda: [0, 0.0])
    busy_total = 0.0
    idle: List[Tuple[str, float]] = []
    used = 0
    for plane in device_planes:
        intervals = []
        for line in plane.lines:
            if line.name == OPS_LINE:
                inside = [ev for ev in line.events
                          if int(ev.end_ns) + shift > lo
                          and int(ev.start_ns) + shift < hi]
                intervals += [(int(ev.start_ns) + shift,
                               int(ev.end_ns) + shift) for ev in inside]
                for ev in _leaves(inside):
                    s = max(int(ev.start_ns) + shift, lo)
                    e = min(int(ev.end_ns) + shift, hi)
                    rec = ops[op_name(ev.name)]
                    rec[0] += 1
                    rec[1] += (e - s) * 1e-9
            elif line.name == MODULES_LINE:
                for ev in line.events:
                    s, e = int(ev.start_ns) + shift, int(ev.end_ns) + shift
                    if e <= lo or s >= hi:
                        continue
                    label = _module_label(ev, programs, enqueued, index)
                    if label:
                        rec = progs[(label, ev.name)]
                        rec[0] += 1
                        rec[1] += (e - s) * 1e-9
        if not intervals:
            continue
        used += 1
        busy = clip(union(intervals), lo, hi)
        busy_total += sum(e - s for s, e in busy) * 1e-9
        for s, e in gaps(busy, lo, hi):
            idle.append((index.at((s + e) // 2) or "no annotated span",
                         (e - s) * 1e-9))
    if not used:
        raise ValueError("the trace holds no operation on a device")
    idle.sort(key=lambda kv: -kv[1])
    return Reduced(window_s=(hi - lo) * 1e-9, busy_s=busy_total / used,
                   ops={k: (v[0], v[1]) for k, v in ops.items()},
                   programs=_nest(progs),
                   idle=idle)


def _nest(progs) -> Dict[str, Dict[str, Tuple[int, float]]]:
    out: Dict[str, Dict[str, Tuple[int, float]]] = {}
    for (label, module), (runs, seconds) in progs.items():
        out.setdefault(label, {})[module] = (runs, seconds)
    return out


def _module_label(ev, programs: Dict[str, str], enqueued: Dict[int, int],
                  index: SpanIndex) -> Optional[str]:
    for part, label in programs.items():
        if part in ev.name:
            return label
    run_id = _stats(ev).get("run_id")
    if run_id is None or int(run_id) not in enqueued:
        return None
    return programs.get(index.at(enqueued[int(run_id)]))


def reduce_dir(trace_dir: str, programs: Dict[str, str],
               span_names: Optional[Iterable[str]] = None) -> Reduced:
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise ValueError(f"expected one trace under {trace_dir}, found "
                         f"{len(files)}")
    return reduce_profile(ProfileData.from_file(files[0]), programs,
                          span_names)
