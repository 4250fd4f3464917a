"""HLO-text analysis: collective byte accounting + host-transfer census.

``cost_analysis()`` does not expose collective traffic, so we parse the
compiled module text and sum operand sizes of every communication op.
Shapes in HLO text look like ``bf16[16,256,4096]{2,1,0}``; the parsed byte
count is the *per-device* payload of one execution of the op (HLO is the
per-device SPMD program).

Ops inside while-loop bodies execute once per trip; the roofline handles
trip multiplication at a higher level (per-unit accounting compiles,
launch/roofline.py) — here :func:`collective_stats` reports, per
collective kind, how many ops/bytes sit inside while bodies vs. at top
level so that mis-accounting is visible (a decode step is one while trip
per layer scan: a collective inside the body runs n_units times).

:func:`host_transfer_ops` lists every op that moves data across the
host/device boundary (send/recv, infeed/outfeed, host-memory-space
copies, ``MoveToHost``-family custom calls) — on the decode path any of
these is a latency cliff, and :mod:`repro.analysis` turns them into
findings.
"""

from __future__ import annotations

import re
from typing import Dict, List, Set, Tuple


def cost_analysis_dict(compiled) -> Dict[str, float]:
    """``compiled.cost_analysis()`` as a plain dict (empty when the backend
    reports nothing)."""
    return dict(compiled.cost_analysis() or {})


def compiled_flops(compiled) -> float:
    """Total compiled FLOPs of a ``jax.stages.Compiled`` (0.0 when the
    backend reports none)."""
    return float(cost_analysis_dict(compiled).get("flops", 0.0))

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?\S+\s*=\s*(\([^)]*\)|\S+?)\s+"
    r"(" + "|".join(_COLLECTIVES) + r")(?:-start|-done)?\(", re.M)


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Sum output-shape bytes per collective kind (one execution each).

    ``*-done`` ops are skipped (their ``*-start`` twin already counted)."""
    out: Dict[str, float] = {k: 0.0 for k in _COLLECTIVES}
    counts: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        if "-done(" in line:
            continue
        m = _OP_RE.match(line)
        if not m:
            continue
        shape_str, kind = m.group(1), m.group(2)
        out[kind] += _shape_bytes(shape_str)
        counts[kind] += 1
    res = {f"{k}_bytes": v for k, v in out.items() if v}
    res.update({f"{k}_count": float(c) for k, c in counts.items() if c})
    res["total_bytes"] = sum(v for k, v in out.items())
    return res


# ---------------------------------------------------------------------------
# Computation segmentation + while-body accounting
# ---------------------------------------------------------------------------

# `%body.7 (arg: (...)) -> (...) {`  or  `ENTRY %main.42 (...) -> ... {`
# Headers always carry a parameter list and a `-> result_type {` tail; op
# lines carry an `=` before their first `(` and never end with `{`.
_COMPUTATION_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*->.*\{\s*$")
_CALLED_RE = re.compile(r"(?:body|condition|to_apply|calls)=%?([\w.\-]+)")
_CALLED_BRACED_RE = re.compile(r"calls=\{([^}]*)\}")


def parse_computations(hlo_text: str) -> Dict[str, List[str]]:
    """Split HLO text into ``{computation_name: [body lines]}``.

    The ENTRY computation is additionally indexed under ``"ENTRY"``."""
    out: Dict[str, List[str]] = {}
    current = None
    for line in hlo_text.splitlines():
        m = _COMPUTATION_RE.match(line.strip())
        if m and not line.strip().startswith("//"):
            current = m.group(2)
            out[current] = []
            if m.group(1):
                out["ENTRY"] = out[current]
            continue
        if line.strip() == "}":
            current = None
            continue
        if current is not None:
            out[current].append(line)
    return out


def _called_computations(lines: List[str]) -> Set[str]:
    called: Set[str] = set()
    for line in lines:
        called.update(_CALLED_RE.findall(line))
        for group in _CALLED_BRACED_RE.findall(line):
            called.update(n.strip().lstrip("%")
                          for n in group.split(",") if n.strip())
    return called


def while_body_computations(hlo_text: str) -> Set[str]:
    """Names of all computations reachable from a ``while`` op's body or
    condition (transitively through fusions/calls)."""
    comps = parse_computations(hlo_text)
    roots: Set[str] = set()
    for lines in comps.values():
        for line in lines:
            if re.search(r"=\s*(\([^)]*\)|\S+)\s+while\(", line):
                roots.update(_CALLED_RE.findall(line))
    seen: Set[str] = set()
    frontier = list(roots)
    while frontier:
        name = frontier.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        frontier.extend(_called_computations(comps[name]))
    return seen


def collective_stats(hlo_text: str) -> Dict[str, float]:
    """Per-kind collective counts/bytes split by while-body membership.

    Returns ``{kind}_count`` / ``{kind}_bytes`` (all occurrences, matching
    :func:`collective_bytes`) plus ``{kind}_in_while_count`` /
    ``{kind}_in_while_bytes`` for the subset staged inside while-loop
    bodies — those run once per trip (n_units trips for the layer-scan),
    so a roofline that reads the flat sum undercounts them."""
    comps = parse_computations(hlo_text)
    in_while = while_body_computations(hlo_text)
    stats: Dict[str, float] = {}

    def bump(key: str, bytes_: int) -> None:
        stats[key + "_count"] = stats.get(key + "_count", 0.0) + 1.0
        stats[key + "_bytes"] = stats.get(key + "_bytes", 0.0) + bytes_

    for name, lines in comps.items():
        if name == "ENTRY":
            continue  # alias of the entry computation's real name
        body = name in in_while
        for line in lines:
            if "-done(" in line:
                continue
            m = _OP_RE.match(line)
            if not m:
                continue
            shape_str, kind = m.group(1), m.group(2)
            nbytes = _shape_bytes(shape_str)
            bump(kind, nbytes)
            if body:
                bump(f"{kind}_in_while", nbytes)
    return stats


# ---------------------------------------------------------------------------
# Host-transfer census
# ---------------------------------------------------------------------------

#: Ops that inherently cross the host/device boundary.
_HOST_OPS = ("send", "send-done", "recv", "recv-done", "infeed", "outfeed")
_HOST_OP_RE = re.compile(
    r"=\s*(?:\([^)]*\)|\S+)\s+(" + "|".join(_HOST_OPS) + r")\(")
#: Custom calls that place/move buffers on host memory.
_HOST_CUSTOM_RE = re.compile(
    r'custom-call\(.*custom_call_target="'
    r'(MoveToHost|MoveToDevice|annotate_device_placement|PinToHost)"',
    re.S)
#: Host memory space annotation in a shape layout, e.g. ``f32[4]{0:S(5)}``.
_HOST_SPACE_RE = re.compile(r"\{[^}]*S\(5\)[^}]*\}")


def host_transfer_ops(hlo_text: str) -> List[Tuple[str, str]]:
    """Every op that moves data between host and device.

    Returns ``(op_kind, stripped_hlo_line)`` pairs: explicit send/recv and
    infeed/outfeed, ``MoveToHost``-family custom calls, and copies whose
    shape layout carries the host memory space ``S(5)``."""
    out: List[Tuple[str, str]] = []
    for line in hlo_text.splitlines():
        s = line.strip()
        m = _HOST_OP_RE.search(s)
        if m:
            out.append((m.group(1), s))
            continue
        m = _HOST_CUSTOM_RE.search(s)
        if m:
            out.append((m.group(1), s))
            continue
        if ("copy" in s or "custom-call" in s) and _HOST_SPACE_RE.search(s):
            out.append(("host-space-copy", s))
    return out


def count_hlo_ops(hlo_text: str) -> Dict[str, int]:
    """Coarse op census for perf archaeology: fusions, convolutions/dots,
    while loops, (re)materialization hints."""
    return {
        "dot": len(re.findall(r"= .*? dot\(", hlo_text)),
        "fusion": len(re.findall(r"fusion\(", hlo_text)),
        "while": len(re.findall(r"= .*? while\(", hlo_text)),
        "gather": len(re.findall(r"= .*? gather\(", hlo_text)),
        "scatter": len(re.findall(r"= .*? scatter\(", hlo_text)),
        "transpose": len(re.findall(r"= .*? transpose\(", hlo_text)),
        "lines": hlo_text.count("\n"),
    }
