"""Reads a JAX profiler trace (``.xplane.pb``) with the parts of its
schema the reduction needs, and nothing of the profiler's own readers:
``jax.profiler.ProfileData`` gives no event's metadata, where a device op
keeps its ``op_name`` (the ``tf_op`` stat), and no module's compiled HLO,
which the ``/host:metadata`` plane holds.

Only the fields named in ``SCHEMA`` are read; the others are skipped as
unknown. Field numbers are those of ``tsl/profiler/protobuf/xplane.proto``
and ``xla/service/hlo.proto``.
"""
from __future__ import annotations

from types import SimpleNamespace
from typing import Dict, List, Tuple

from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

_T = descriptor_pb2.FieldDescriptorProto
INT64, UINT64, STRING, BYTES = (_T.TYPE_INT64, _T.TYPE_UINT64,
                                _T.TYPE_STRING, _T.TYPE_BYTES)
REPEATED = "repeated"

# message -> (field, number, scalar type or message name[, REPEATED])
SCHEMA = {
    "XSpace": [("planes", 1, "XPlane", REPEATED)],
    "XPlane": [("name", 2, STRING), ("lines", 3, "XLine", REPEATED),
               ("event_metadata", 4, "EventMetadataEntry", REPEATED),
               ("stat_metadata", 5, "StatMetadataEntry", REPEATED)],
    "EventMetadataEntry": [("key", 1, INT64), ("value", 2, "XEventMetadata")],
    "StatMetadataEntry": [("key", 1, INT64), ("value", 2, "XStatMetadata")],
    "XLine": [("name", 2, STRING), ("timestamp_ns", 3, INT64),
              ("events", 4, "XEvent", REPEATED)],
    "XEvent": [("metadata_id", 1, INT64), ("offset_ps", 2, INT64),
               ("duration_ps", 3, INT64)],
    "XStat": [("metadata_id", 1, INT64), ("str_value", 5, STRING),
              ("bytes_value", 6, BYTES), ("ref_value", 7, UINT64)],
    "XEventMetadata": [("id", 1, INT64), ("name", 2, STRING),
                       ("stats", 5, "XStat", REPEATED)],
    "XStatMetadata": [("id", 1, INT64), ("name", 2, STRING)],
    "HloProto": [("hlo_module", 1, "HloModuleProto")],
    "HloModuleProto": [("name", 1, STRING),
                       ("computations", 3, "HloComputationProto", REPEATED)],
    "HloComputationProto": [
        ("name", 1, STRING),
        ("instructions", 2, "HloInstructionProto", REPEATED),
        ("id", 5, INT64)],
    "HloInstructionProto": [("name", 1, STRING), ("opcode", 2, STRING),
                            ("metadata", 7, "OpMetadata"),
                            ("called_computation_ids", 38, INT64, REPEATED)],
    "OpMetadata": [("op_name", 2, STRING)],
}
PACKAGE = "bench_xplane"
OP_NAME_STAT, HLO_STAT = "tf_op", "Hlo Proto"
METADATA_PLANE = "/host:metadata"


def _classes() -> Dict[str, type]:
    f = descriptor_pb2.FileDescriptorProto(name=f"{PACKAGE}.proto",
                                           package=PACKAGE, syntax="proto2")
    for msg, fields in SCHEMA.items():
        m = f.message_type.add(name=msg)
        for name, number, typ, *rep in fields:
            fd = m.field.add(name=name, number=number,
                             label=_T.LABEL_REPEATED if rep
                             else _T.LABEL_OPTIONAL)
            if isinstance(typ, str):
                fd.type, fd.type_name = _T.TYPE_MESSAGE, f".{PACKAGE}.{typ}"
            else:
                fd.type = typ
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return {n: message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{PACKAGE}.{n}")) for n in SCHEMA}


MESSAGES = _classes()


def _stat_str(stat, names: Dict[int, str]) -> str:
    return stat.str_value if stat.HasField("str_value") else names.get(
        stat.ref_value, "")


def read(path: str) -> Tuple[List[SimpleNamespace], Dict[str, object]]:
    """The planes of the trace at ``path``, each ``name`` and ``lines``,
    each line ``name`` and ``events``, each event ``name``, ``start_ns``,
    ``duration_ns`` and ``op_name`` (its metadata's ``tf_op``, or None);
    and each module's compiled HLO (an ``HloModuleProto``) by the module's
    name as its ``XLA Modules`` events carry it."""
    space = MESSAGES["XSpace"]()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    planes, hlo = [], {}
    for p in space.planes:
        stat_names = {e.key: e.value.name for e in p.stat_metadata}
        meta = {}
        for e in p.event_metadata:
            md = e.value
            stats = {stat_names.get(s.metadata_id): s for s in md.stats}
            op = stats.get(OP_NAME_STAT)
            meta[e.key] = (md.name,
                           _stat_str(op, stat_names) if op else None)
            if p.name == METADATA_PLANE and HLO_STAT in stats:
                proto = MESSAGES["HloProto"]()
                proto.ParseFromString(stats[HLO_STAT].bytes_value)
                hlo[md.name] = proto.hlo_module
        lines = []
        for ln in p.lines:
            t0 = ln.timestamp_ns
            events = []
            for ev in ln.events:
                name, op_name = meta.get(ev.metadata_id, ("", None))
                # whole nanoseconds, as jax.profiler.ProfileData gives them
                events.append(SimpleNamespace(
                    name=name, start_ns=float(t0 + ev.offset_ps // 1000),
                    duration_ns=float(ev.duration_ps // 1000),
                    op_name=op_name))
            lines.append(SimpleNamespace(name=ln.name, events=events))
        planes.append(SimpleNamespace(name=p.name, lines=lines))
    return planes, hlo
