"""A reader of the profiler's ``.xplane.pb`` files, with only the fields the
benchmark reads.

The schema below is the subset of ``tsl/profiler/protobuf/xplane.proto``
(XSpace > XPlane > XLine > XEvent, with event names held once per plane in
``event_metadata``) that a trace reduction needs; the parser skips every
other field. Parsing with the compiled protobuf runtime and keeping event
names as ids makes a trace of millions of events readable in seconds.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np
from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

_F = descriptor_pb2.FieldDescriptorProto


def _field(msg, name, number, kind, label=_F.LABEL_OPTIONAL, type_name=None):
    f = msg.field.add(name=name, number=number, type=kind, label=label)
    if type_name:
        f.type_name = type_name


def _schema():
    fd = descriptor_pb2.FileDescriptorProto(name="bench_xplane.proto", package="benchxplane",
                                            syntax="proto3")
    meta = fd.message_type.add(name="XEventMetadata")
    _field(meta, "id", 1, _F.TYPE_INT64)
    _field(meta, "name", 2, _F.TYPE_STRING)
    _field(meta, "display_name", 4, _F.TYPE_STRING)
    ev = fd.message_type.add(name="XEvent")
    _field(ev, "metadata_id", 1, _F.TYPE_INT64)
    _field(ev, "offset_ps", 2, _F.TYPE_INT64)
    _field(ev, "duration_ps", 3, _F.TYPE_INT64)
    line = fd.message_type.add(name="XLine")
    _field(line, "id", 1, _F.TYPE_INT64)
    _field(line, "name", 2, _F.TYPE_STRING)
    _field(line, "timestamp_ns", 3, _F.TYPE_INT64)
    _field(line, "events", 4, _F.TYPE_MESSAGE, _F.LABEL_REPEATED, ".benchxplane.XEvent")
    plane = fd.message_type.add(name="XPlane")
    entry = plane.nested_type.add(name="EventMetadataEntry")
    entry.options.map_entry = True
    _field(entry, "key", 1, _F.TYPE_INT64)
    _field(entry, "value", 2, _F.TYPE_MESSAGE, type_name=".benchxplane.XEventMetadata")
    _field(plane, "id", 1, _F.TYPE_INT64)
    _field(plane, "name", 2, _F.TYPE_STRING)
    _field(plane, "lines", 3, _F.TYPE_MESSAGE, _F.LABEL_REPEATED, ".benchxplane.XLine")
    _field(plane, "event_metadata", 4, _F.TYPE_MESSAGE, _F.LABEL_REPEATED,
           ".benchxplane.XPlane.EventMetadataEntry")
    space = fd.message_type.add(name="XSpace")
    _field(space, "planes", 1, _F.TYPE_MESSAGE, _F.LABEL_REPEATED, ".benchxplane.XPlane")
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(pool.FindMessageTypeByName("benchxplane.XSpace"))


XSpace = _schema()


@dataclasses.dataclass
class Line:
    name: str
    ids: np.ndarray  # (n,) event metadata ids
    start_ns: np.ndarray  # (n,) float64, the trace's clock
    end_ns: np.ndarray


@dataclasses.dataclass
class Plane:
    name: str
    names: Dict[int, str]  # event metadata id -> event name
    lines: List[Line]

    def line(self, name: str):
        return next((ln for ln in self.lines if ln.name == name), None)


def read(path: str) -> List[Plane]:
    space = XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    planes = []
    for p in space.planes:
        names = {k: v.name for k, v in p.event_metadata.items()}
        lines = []
        for ln in p.lines:
            n = len(ln.events)
            ids = np.empty(n, np.int64)
            off = np.empty(n, np.float64)
            dur = np.empty(n, np.float64)
            for i, ev in enumerate(ln.events):
                ids[i] = ev.metadata_id
                off[i] = ev.offset_ps
                dur[i] = ev.duration_ps
            start = ln.timestamp_ns + off / 1e3
            lines.append(Line(ln.name, ids, start, start + dur / 1e3))
        planes.append(Plane(p.name, names, lines))
    return planes
