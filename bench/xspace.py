"""The device's operations with the framework scope each carries, read
from a profiler trace (``.xplane.pb``).

``jax.profiler.ProfileData`` gives an event's own statistics but not those
of its metadata, where the TPU profiler keeps an XLA operation's
framework name (``tf_op``: the jitted function's ``jax.named_scope`` path,
e.g. ``jit(decode_body)/while/body/mla/dot_general``).  So the trace is
parsed here with the protobuf runtime against the fields of the profiler's
``XSpace`` message that are needed (unknown fields are skipped).
"""

from __future__ import annotations

import functools
import re
from collections import defaultdict

from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"

# (message, [(field, number, type, repeated, message type)]); the fields
# of ONEOFS lie in a oneof, as in the profiler's schema, so that a zero
# value is still written out
_I64, _U64, _DBL, _STR, _BYTES, _MSG = 3, 4, 1, 9, 12, 11
_SCHEMA = [
    ("XSpace", [("planes", 1, _MSG, True, "XPlane")]),
    ("XPlane", [("id", 1, _I64, False, None), ("name", 2, _STR, False, None),
                ("lines", 3, _MSG, True, "XLine"),
                ("event_metadata", 4, _MSG, True, "EventMetadataEntry"),
                ("stat_metadata", 5, _MSG, True, "StatMetadataEntry")]),
    ("EventMetadataEntry", [("key", 1, _I64, False, None),
                            ("value", 2, _MSG, False, "XEventMetadata")]),
    ("StatMetadataEntry", [("key", 1, _I64, False, None),
                           ("value", 2, _MSG, False, "XStatMetadata")]),
    ("XLine", [("id", 1, _I64, False, None), ("name", 2, _STR, False, None),
               ("timestamp_ns", 3, _I64, False, None), ("events", 4, _MSG, True, "XEvent")]),
    ("XEvent", [("metadata_id", 1, _I64, False, None), ("offset_ps", 2, _I64, False, None),
                ("duration_ps", 3, _I64, False, None), ("stats", 4, _MSG, True, "XStat")]),
    ("XStat", [("metadata_id", 1, _I64, False, None), ("double_value", 2, _DBL, False, None),
               ("uint64_value", 3, _U64, False, None), ("int64_value", 4, _I64, False, None),
               ("str_value", 5, _STR, False, None), ("bytes_value", 6, _BYTES, False, None),
               ("ref_value", 7, _U64, False, None)]),
    ("XEventMetadata", [("id", 1, _I64, False, None), ("name", 2, _STR, False, None),
                        ("display_name", 4, _STR, False, None),
                        ("stats", 5, _MSG, True, "XStat")]),
    ("XStatMetadata", [("id", 1, _I64, False, None), ("name", 2, _STR, False, None)]),
]
_PACKAGE = "bench_xspace"
ONEOFS = {"XStat": ("value", {"double_value", "uint64_value", "int64_value", "str_value",
                              "bytes_value", "ref_value"}),
          "XEvent": ("data", {"offset_ps"})}


@functools.lru_cache(maxsize=None)
def messages() -> dict:
    """Message classes of the schema above, by name."""
    fdp = descriptor_pb2.FileDescriptorProto(name=f"{_PACKAGE}.proto", package=_PACKAGE,
                                             syntax="proto3")
    for name, fields in _SCHEMA:
        msg = fdp.message_type.add(name=name)
        oneof, members = ONEOFS.get(name, (None, ()))
        if oneof:
            msg.oneof_decl.add(name=oneof)
        for fname, number, ftype, repeated, mtype in fields:
            f = msg.field.add(name=fname, number=number, type=ftype)
            if fname in members:
                f.oneof_index = 0
            f.label = (descriptor_pb2.FieldDescriptorProto.LABEL_REPEATED if repeated
                       else descriptor_pb2.FieldDescriptorProto.LABEL_OPTIONAL)
            if mtype:
                f.type_name = f".{_PACKAGE}.{mtype}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return {name: message_factory.GetMessageClass(pool.FindMessageTypeByName(f"{_PACKAGE}.{name}"))
            for name, _ in _SCHEMA}


def _stat_text(stat, stat_names: dict) -> str:
    if stat.str_value:
        return stat.str_value
    if stat.ref_value:
        return stat_names.get(stat.ref_value, "")
    return ""


def device_ops(data: bytes) -> dict:
    """``{device index: [(start_ns, end_ns, text)]}`` of every operation on
    the ``XLA Ops`` line of each TPU plane; ``text`` joins the operation's
    name and the string statistics of it and its metadata (the framework
    scope among them), separated by newlines."""
    space = messages()["XSpace"]()
    space.ParseFromString(data)
    out = defaultdict(list)
    for plane in space.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        meta = {}
        for e in plane.event_metadata:
            md = e.value
            meta[e.key] = "\n".join([md.name, md.display_name]
                                    + [_stat_text(s, stat_names) for s in md.stats])
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                start = line.timestamp_ns + ev.offset_ps / 1000.0
                text = meta.get(ev.metadata_id, "")
                extra = [_stat_text(s, stat_names) for s in ev.stats]
                if any(extra):
                    text = "\n".join([text] + extra)
                out[int(m.group(1))].append((start, start + ev.duration_ps / 1000.0, text))
    return dict(out)
