"""Split the device's idle share by what the host was doing.

The trace reduction labels every idle gap of the chip with the innermost
program span the host was in at the gap's middle (``Reduced.idle``).
Each label falls in exactly one class:

* ``no_span``: the host was in none of the program's spans (the label is
  not one of ``Record.span_names``);
* ``program_io``: a device program's inputs, enqueue or results: spans
  under ``decode.`` or ``prefill.``, and ``kv.cow``;
* ``host_loop``: every other program span, the serve loop's own work
  (``sample``, ``schedule.admit``, ``kv.grow``, ``retire``, and
  ``serve.iteration`` between them, ...).

Each share is the class's idle seconds over the window (one device), so
the three sum to the device's idle share.
"""

from __future__ import annotations

from typing import Iterable

CLASSES = ("no_span", "program_io", "host_loop")
PROGRAM_IO_PREFIXES = ("decode.", "prefill.")
PROGRAM_IO_NAMES = ("kv.cow",)


def classify(label: str, span_names: Iterable[str]) -> str:
    if label not in span_names:
        return "no_span"
    if label.startswith(PROGRAM_IO_PREFIXES) or label in PROGRAM_IO_NAMES:
        return "program_io"
    return "host_loop"


def share(ctx, cls: str):
    """Percent of the traced window in which the chip was idle while the
    host was in a span of class ``cls``; None in an untraced run."""
    if cls not in CLASSES:
        raise ValueError(f"unknown idle class {cls!r}; known: {CLASSES}")
    if not ctx.trace:
        return None
    names = set(ctx.record.span_names)
    idle = sum(s for label, s in ctx.trace.idle
               if classify(label, names) == cls)
    return 100.0 * idle / ctx.trace.window_s
