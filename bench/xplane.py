"""A reader of the profiler's `.xplane.pb` that keeps what
`jax.profiler.ProfileData` drops: the stats of each event's metadata.

XLA records each device op's JAX name stack in its event metadata, as the
`tf_op` stat (e.g. `jit(dda_scan_allcomm)/while/body/dda.subgrad/sub`);
`ProfileData` exposes only the stats of events. The messages are declared
here, from the profiler's `xplane.proto`, so that reading them needs
`protobuf` alone and no TensorFlow.
"""

from __future__ import annotations

import dataclasses
import functools

#: the stat of a device op's metadata that holds its name stack
TF_OP = "tf_op"

# (message, [(field, number, type, label, message type)]); label 3 repeats
_MESSAGES = [
    ("XSpace", [("planes", 1, "message", 3, "XPlane")]),
    ("XPlane", [("id", 1, "int64", 1, None), ("name", 2, "string", 1, None),
                ("lines", 3, "message", 3, "XLine"),
                ("event_metadata", 4, "message", 3,
                 "XPlane.EventMetadataEntry"),
                ("stat_metadata", 5, "message", 3,
                 "XPlane.StatMetadataEntry")]),
    ("XLine", [("id", 1, "int64", 1, None), ("name", 2, "string", 1, None),
               ("timestamp_ns", 3, "int64", 1, None),
               ("events", 4, "message", 3, "XEvent")]),
    ("XEvent", [("metadata_id", 1, "int64", 1, None),
                ("offset_ps", 2, "int64", 1, None),
                ("duration_ps", 3, "int64", 1, None)]),
    ("XStat", [("metadata_id", 1, "int64", 1, None),
               ("str_value", 5, "string", 1, None),
               ("ref_value", 7, "uint64", 1, None)]),
    ("XEventMetadata", [("id", 1, "int64", 1, None),
                        ("name", 2, "string", 1, None),
                        ("stats", 5, "message", 3, "XStat")]),
    ("XStatMetadata", [("id", 1, "int64", 1, None),
                       ("name", 2, "string", 1, None)]),
]
_MAPS = [("EventMetadataEntry", "XEventMetadata"),
         ("StatMetadataEntry", "XStatMetadata")]


@functools.lru_cache(maxsize=None)
def _space_class():
    from google.protobuf import descriptor_pb2, descriptor_pool, \
        message_factory

    F = descriptor_pb2.FieldDescriptorProto
    types = {"int64": F.TYPE_INT64, "uint64": F.TYPE_UINT64,
             "string": F.TYPE_STRING, "message": F.TYPE_MESSAGE}
    pkg = "bench_xplane"
    fdp = descriptor_pb2.FileDescriptorProto(name="bench_xplane.proto",
                                             package=pkg, syntax="proto3")

    def add(msg, name, number, kind, label, type_name):
        f = msg.field.add(name=name, number=number, type=types[kind],
                          label=label)
        if type_name:
            f.type_name = f".{pkg}.{type_name}"

    for name, fields in _MESSAGES:
        msg = fdp.message_type.add(name=name)
        for field in fields:
            add(msg, *field)
        if name == "XPlane":
            for entry, value in _MAPS:
                m = msg.nested_type.add(name=entry)
                m.options.map_entry = True
                add(m, "key", 1, "int64", 1, None)
                add(m, "value", 2, "message", 1, value)
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{pkg}.XSpace"))


@dataclasses.dataclass(frozen=True)
class Event:
    name: str      # the event metadata's name
    start: float   # ns, on the trace's clock, as `ProfileData` gives it
    end: float
    tf_op: str     # the metadata's `tf_op` stat, else ""


def read(path: str) -> dict[str, dict[str, list[Event]]]:
    """plane name -> line name -> the line's events, as recorded, of the
    trace file at `path`."""
    space = _space_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    planes: dict[str, dict[str, list[Event]]] = {}
    for plane in space.planes:
        stats = plane.stat_metadata
        tf_op = next((k for k, v in stats.items() if v.name == TF_OP), None)
        meta = {k: (m.name, next((_str(s, stats) for s in m.stats
                                  if s.metadata_id == tf_op), ""))
                for k, m in plane.event_metadata.items()}
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            events = lines.setdefault(line.name, [])
            for e in line.events:
                name, op = meta.get(e.metadata_id, ("", ""))
                # whole nanoseconds, as `ProfileData` rounds them
                start = line.timestamp_ns + e.offset_ps // 1000
                events.append(Event(name, start,
                                    start + e.duration_ps // 1000, op))
    return planes


def _str(stat, stats) -> str:
    """A stat's string: its own, or that of the stat metadata it refers
    to (how the profiler stores a string that repeats)."""
    if stat.str_value:
        return stat.str_value
    return stats[stat.ref_value].name if stat.ref_value in stats else ""
