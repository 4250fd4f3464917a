"""One run of one cell: set-up, measured window, check, result line."""

from __future__ import annotations

import dataclasses
import json
import math
import os
import shutil
import sys
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional

from . import device as DV
from . import spec

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: JAX's persistent compilation cache: a fixed directory inside the
#: checkout, so that two checkouts share nothing and only the first run
#: of a cell in a checkout compiles.
CACHE_DIR = spec.ROOT / ".jax_cache"
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def give_cache_dir() -> None:
    """Hand the checkout's cache directory to JAX and to the program's
    own cache helper, which both take it from the environment; JAX reads
    it when it is imported, so this comes before the first import."""
    os.environ[CACHE_ENV] = str(CACHE_DIR)


def use_compile_cache() -> None:
    """Turn the cache on with the program's helper, and keep every
    program there, however short its compilation."""
    import jax
    from repro.launch.compile_cache import setup_compile_cache
    where = setup_compile_cache()
    if where != str(CACHE_DIR) or \
            jax.config.jax_compilation_cache_dir != str(CACHE_DIR):
        raise RuntimeError(f"compile cache at {where}, not {CACHE_DIR}: "
                           "give_cache_dir() must come before jax is "
                           "imported")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log(f"compile cache: {CACHE_DIR}")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileClock:
    """Counts this process's backend compile events and their seconds;
    a program loaded from the persistent cache counts too, briefly, so
    any event inside the window means a shape was not warmed up."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.count += 1
            self.seconds += duration


@dataclasses.dataclass
class Check:
    """One number compared against its limit (``value <= limit``)."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclasses.dataclass
class Run:
    """What a driver gets: the cell, its reference and the run's knobs."""
    cell: spec.Cell
    reference: Any
    seed: int
    seconds: float
    devices: List[Any]
    t_start: float
    compiles: CompileClock
    trace_dir: Optional[str] = None
    log: Callable[[str], None] = log


@dataclasses.dataclass
class Record:
    """What a driver returns."""
    end_to_end: Dict[str, float]
    attempted: int
    failed: int
    checks: List[Check]
    memory_peak_bytes: int
    window_s: float
    #: counters and readings the per-layer metric readers use
    data: Dict[str, Any] = dataclasses.field(default_factory=dict)
    #: device programs by name, for the trace reduction: substring of
    #: the device module's name -> label
    programs: Dict[str, str] = dataclasses.field(default_factory=dict)
    #: the annotated host spans of the trace
    span_names: List[str] = dataclasses.field(default_factory=list)
    #: what the check compared, for the control (``calibrate.py``)
    check_items: Any = None

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c.ok for c in self.checks)


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader gets."""
    cell: spec.Cell
    record: Record
    trace: Any            # chipbench.tracing.Reduced, None untraced
    peaks: Dict[str, Any]


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             devices, t_start: float) -> Dict[str, Any]:
    """Run ``cell`` once and return its result line (a dict)."""
    from . import tracing
    compiles = CompileClock()
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    try:
        run = Run(cell=cell, reference=spec.reference_module(cell.config),
                  seed=seed, seconds=seconds, devices=devices,
                  t_start=t_start, compiles=compiles, trace_dir=trace_dir)
        rec = spec.driver_module(cell.config).run(run)
        reduced = (tracing.reduce_dir(trace_dir, rec.programs,
                                       rec.span_names)
                   if trace else None)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    dev = DV.describe(devices)
    dev["memory_peak_bytes"] = rec.memory_peak_bytes
    metrics: Dict[str, Dict[str, Any]] = {}
    line: Dict[str, Any] = {"correct": rec.correct,
                            "attempted": rec.attempted,
                            "failed": rec.failed}
    if not trace:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": rec.end_to_end[m["name"]],
                                  "unit": m["unit"]}
    else:
        ctx = Context(cell=cell, record=rec, trace=reduced,
                      peaks=DV.peaks(dev["kind"]))
        for m in cell.per_layer:
            value = spec.metric_reader(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        dev["busy_s"] = reduced.busy_s
        dev["window_s"] = reduced.window_s
        line["breakdown"] = reduced.breakdown()
    line["metrics"] = metrics
    line["device"] = dev
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in rec.checks}
    log(f"window {rec.window_s:.3f} s; compilations in the process "
        f"{compiles.count} ({compiles.seconds:.1f} s); "
        f"{time.perf_counter() - t_start:.3f} s from the start to the result")
    for c in rec.checks:
        log(f"check {c.name}: {c.value!r} <= limit {c.limit!r}: "
            f"{'ok' if c.ok else 'FAILED'}")
    return line


def emit(line: Dict[str, Any]) -> None:
    print(json.dumps(line), flush=True)
