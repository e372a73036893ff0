"""Device time by stage of the decomposition, from the ``tucker.*`` scopes
the program names (``repro.core.stages``).

The profiler keeps each XLA operation's ``op_name`` path as the ``tf_op``
stat of its event metadata, for example
``jit(_scan_sweeps_impl)/while/body/.../tucker.kron/pallas_call``.
``bench/xplane.py`` keeps only the operations' HLO text; this module reads
the stat from the same file, with a schema of its own (``XPlane.stat_metadata``
and ``XEventMetadata.stats`` besides the plane's name and lines), for the
chips that ``bench/trace.reduce`` reduces, in the same order. A reduced
trace and these paths then give the device seconds of each stage.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional

import numpy as np
from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

from bench import trace
from bench.xplane import _F, _field

STAGE = re.compile(r"tucker\.[a-z_]+")
TF_OP = "tf_op"


def _schema():
    fd = descriptor_pb2.FileDescriptorProto(name="bench_scopes.proto", package="benchscopes",
                                            syntax="proto3")
    stat = fd.message_type.add(name="XStat")
    _field(stat, "metadata_id", 1, _F.TYPE_INT64)
    _field(stat, "str_value", 5, _F.TYPE_STRING)
    _field(stat, "ref_value", 7, _F.TYPE_UINT64)
    smeta = fd.message_type.add(name="XStatMetadata")
    _field(smeta, "id", 1, _F.TYPE_INT64)
    _field(smeta, "name", 2, _F.TYPE_STRING)
    meta = fd.message_type.add(name="XEventMetadata")
    _field(meta, "id", 1, _F.TYPE_INT64)
    _field(meta, "stats", 5, _F.TYPE_MESSAGE, _F.LABEL_REPEATED, ".benchscopes.XStat")
    line = fd.message_type.add(name="XLine")
    _field(line, "name", 2, _F.TYPE_STRING)
    plane = fd.message_type.add(name="XPlane")
    for field_name, entry_name, number, value in (
            ("event_metadata", "EventMetadataEntry", 4, "XEventMetadata"),
            ("stat_metadata", "StatMetadataEntry", 5, "XStatMetadata")):
        entry = plane.nested_type.add(name=entry_name)
        entry.options.map_entry = True
        _field(entry, "key", 1, _F.TYPE_INT64)
        _field(entry, "value", 2, _F.TYPE_MESSAGE, type_name=f".benchscopes.{value}")
        _field(plane, field_name, number, _F.TYPE_MESSAGE, _F.LABEL_REPEATED,
               f".benchscopes.XPlane.{entry_name}")
    _field(plane, "name", 2, _F.TYPE_STRING)
    _field(plane, "lines", 3, _F.TYPE_MESSAGE, _F.LABEL_REPEATED, ".benchscopes.XLine")
    space = fd.message_type.add(name="XSpace")
    _field(space, "planes", 1, _F.TYPE_MESSAGE, _F.LABEL_REPEATED, ".benchscopes.XPlane")
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(pool.FindMessageTypeByName("benchscopes.XSpace"))


XSpace = _schema()


def op_paths(path: str, n_devices: int) -> List[Dict[int, str]]:
    """Per chip, as ``trace.reduce`` orders them: event metadata id -> the
    operation's ``tf_op`` path (ids without one are left out)."""
    space = XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    chips = sorted((p for p in space.planes if p.name.startswith("/device:")
                    and any(ln.name == trace.OPS_LINE for ln in p.lines)),
                   key=lambda p: p.name)[:n_devices]
    out = []
    for p in chips:
        tf_op = [k for k, v in p.stat_metadata.items() if v.name == TF_OP]
        paths = {}
        for k, meta in p.event_metadata.items():
            for st in meta.stats:
                if st.metadata_id in tf_op:
                    paths[k] = st.str_value or p.stat_metadata[st.ref_value].name
        out.append(paths)
    return out


def stage(op_path: str) -> Optional[str]:
    """The ``tucker.*`` scope in an operation's path, or ``None``; under a
    transform it reads ``vmap(tucker.init)``."""
    found = STAGE.findall(op_path)
    return found[-1] if found else None


def _stages(device: trace.Device, paths: Dict[int, str]) -> np.ndarray:
    return np.array([stage(paths.get(int(k), "")) or "" for k in device.ids], dtype=object)


def stage_seconds(reduced: trace.Reduced, paths: List[Dict[int, str]]) -> Dict[str, float]:
    """Device seconds per stage in the window, averaged over the chips."""
    out: Dict[str, float] = {}
    for d, p in zip(reduced.devices, paths):
        st = _stages(d, p)
        dur = d.spans[:, 1] - d.spans[:, 0]
        for name in set(st) - {""}:
            out[name] = out.get(name, 0.0) + float(np.sum(dur[st == name])) / 1e9
    return {k: v / reduced.n_devices for k, v in sorted(out.items())}


def scoped_share(reduced: trace.Reduced, paths: List[Dict[int, str]]) -> float:
    """Share of the chips' busy time that falls under some stage."""
    scoped = busy = 0.0
    for d, p in zip(reduced.devices, paths):
        mine = _stages(d, p) != ""
        scoped += trace.measure(trace.union(d.spans[mine]))
        busy += trace.measure(d.busy)
    return scoped / busy if busy else 0.0
