"""Device time of a program's ops by the scope they were traced under.

On a TPU the profiler's trace gives each op's metadata two stats beside
its name: ``program_id``, the module the op belongs to, and ``tf_op``,
the op's JAX name stack (``jit(decode_step_paged)/while/body/b0_attn/
moe.experts/dot_general``), which carries every ``jax.named_scope`` the
op was traced under.  ``jax.profiler.ProfileData`` does not show
metadata stats, so the ``.xplane.pb`` file is read here with a message
layout declared for the few fields needed (the rest of the file is
skipped as unknown fields).

:func:`scope_ms` sums the device time of the innermost op events of one
module whose name stack holds a scope, per run of that module.
"""

from __future__ import annotations

import functools
import glob
import os
import re
from typing import Dict, Iterable, List, Tuple

DEVICE_PLANE = "/device:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

#: The fields of ``tsl/profiler/protobuf/xplane.proto`` read here, by
#: message: (name, number, type, repeated).  Its maps are declared as
#: repeated entries, which is the same wire form.
_LAYOUT = {
    "XSpace": [("planes", 1, "XPlane", True)],
    "XPlane": [("name", 2, "string", False), ("lines", 3, "XLine", True),
               ("event_metadata", 4, "EventMetadataEntry", True),
               ("stat_metadata", 5, "StatMetadataEntry", True)],
    "EventMetadataEntry": [("key", 1, "int64", False),
                           ("value", 2, "XEventMetadata", False)],
    "StatMetadataEntry": [("key", 1, "int64", False),
                          ("value", 2, "XStatMetadata", False)],
    "XLine": [("name", 2, "string", False),
              ("timestamp_ns", 3, "int64", False),
              ("events", 4, "XEvent", True)],
    "XEvent": [("metadata_id", 1, "int64", False),
               ("offset_ps", 2, "int64", False),
               ("duration_ps", 3, "int64", False)],
    "XEventMetadata": [("id", 1, "int64", False), ("name", 2, "string", False),
                       ("stats", 5, "XStat", True)],
    "XStatMetadata": [("id", 1, "int64", False), ("name", 2, "string", False)],
    "XStat": [("metadata_id", 1, "int64", False),
              ("uint64_value", 3, "uint64", False),
              ("int64_value", 4, "int64", False),
              ("str_value", 5, "string", False),
              ("ref_value", 7, "uint64", False)],
}


@functools.lru_cache(maxsize=1)
def _xspace_class():
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory
    F = descriptor_pb2.FieldDescriptorProto
    scalar = {"string": F.TYPE_STRING, "int64": F.TYPE_INT64,
              "uint64": F.TYPE_UINT64}
    proto = descriptor_pb2.FileDescriptorProto(
        name="chipbench_xplane.proto", package="chipbench_xplane",
        syntax="proto3")
    for msg, fields in _LAYOUT.items():
        m = proto.message_type.add(name=msg)
        for name, number, kind, repeated in fields:
            f = m.field.add(name=name, number=number,
                            label=F.LABEL_REPEATED if repeated
                            else F.LABEL_OPTIONAL)
            if kind in scalar:
                f.type = scalar[kind]
            else:
                f.type = F.TYPE_MESSAGE
                f.type_name = f".chipbench_xplane.{kind}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(proto)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("chipbench_xplane.XSpace"))


def read_xspace(path: str):
    space = _xspace_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return space


def _op_meta(plane) -> Dict[int, Tuple[int, str]]:
    """Event metadata id -> (program id, name stack) of the plane's ops
    that carry a name stack."""
    stat_names = {e.key: e.value.name for e in plane.stat_metadata}
    out = {}
    for e in plane.event_metadata:
        stats = {}
        for s in e.value.stats:
            name = stat_names.get(s.metadata_id)
            if name == "tf_op":
                stats[name] = (s.str_value if s.str_value
                               else stat_names.get(s.ref_value, ""))
            elif name == "program_id":
                stats[name] = s.uint64_value or s.int64_value
        if stats.get("tf_op"):
            out[e.key] = (stats.get("program_id"), stats["tf_op"])
    return out


def _leaves(intervals: List[Tuple[int, int, int]]) -> List[Tuple[int, int, int]]:
    """Events (start, end, id) that hold no other event (a loop's op
    spans the ops of its body)."""
    evs = sorted(intervals, key=lambda e: (e[0], -e[1]))
    return [ev for i, ev in enumerate(evs)
            if i + 1 == len(evs) or evs[i + 1][0] >= ev[1]]


def scope_ms(space, module: str, scopes: Iterable[str]) -> Dict[str, float]:
    """Device milliseconds per run of the module named ``module`` (the
    jitted function's name, ``jit_<fn>``) spent in ops whose name stack
    holds each of ``scopes``, summed over the devices' planes.  Empty
    where no run of the module or no op's name stack is in the trace."""
    pattern = re.compile(re.escape(module) + r"\((\d+)\)")
    scopes = list(scopes)
    runs, ps = 0, dict.fromkeys(scopes, 0)
    stacks_seen = False
    for plane in space.planes:
        if not plane.name.startswith(DEVICE_PLANE):
            continue
        names = {e.key: e.value.name for e in plane.event_metadata}
        programs = set()
        for line in plane.lines:
            if line.name == MODULES_LINE:
                for ev in line.events:
                    m = pattern.fullmatch(names.get(ev.metadata_id, ""))
                    if m:
                        programs.add(int(m.group(1)))
                        runs += 1
        meta = _op_meta(plane)
        stacks_seen = stacks_seen or bool(meta)
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            ops = [(ev.offset_ps, ev.offset_ps + ev.duration_ps,
                    ev.metadata_id) for ev in line.events]
            for start, end, mid in _leaves(ops):
                program, stack = meta.get(mid, (None, ""))
                if program not in programs:
                    continue
                for scope in scopes:
                    if f"/{scope}/" in stack:
                        ps[scope] += end - start
    if not runs or not stacks_seen:
        return {}
    return {scope: v * 1e-9 / runs for scope, v in ps.items()}


def scope_ms_dir(trace_dir: str, module: str,
                 scopes: Iterable[str]) -> Dict[str, float]:
    """:func:`scope_ms` of the one trace under ``trace_dir``."""
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise ValueError(f"expected one trace under {trace_dir}, found "
                         f"{len(files)}")
    return scope_ms(read_xspace(files[0]), module, scopes)
