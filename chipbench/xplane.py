"""The op metadata of a profiler trace, read from its ``.xplane.pb``.

``jax.profiler.ProfileData`` gives each device op's name, the optimised
HLO instruction, and hides the rest of the op's metadata. The file holds
it: each ``/device:TPU:<n>`` plane has an ``event_metadata`` table keyed
by that same name, whose entries carry stats, among them ``tf_op``, the
op's name stack (``jit(_social_scan_core)/while/body/consensus/...:``):
every ``jax.named_scope`` the program opened around the op.

:func:`tf_ops` reads just those fields with a small reader of the protobuf
wire format (no protobuf package needed), of these messages of XSpace:

* ``XSpace``: ``planes`` = 1;
* ``XPlane``: ``name`` = 2, ``lines`` = 3 (skipped by its length),
  ``event_metadata`` = 4, ``stat_metadata`` = 5 (maps: key = 1, value = 2);
* ``XEventMetadata``: ``name`` = 2, ``stats`` = 5;
* ``XStatMetadata``: ``id`` = 1, ``name`` = 2;
* ``XStat``: ``metadata_id`` = 1, ``str_value`` = 5, ``ref_value`` = 7 (the
  id of a stat metadata entry whose name is the string).
"""
from __future__ import annotations

import re

DEVICE_PLANE = re.compile(r"/device:TPU:\d+")
TF_OP = "tf_op"


def _varint(buf, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, i


def fields(buf):
    """``(field number, value)`` of each field of one message: an int for a
    varint or a fixed-width field, a ``memoryview`` of the bytes for a
    length-delimited one (a string, bytes, or a nested message)."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 5:
            value, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield key >> 3, value


def _map_values(entry) -> memoryview | None:
    return next((v for f, v in fields(entry) if f == 2), None)


def _plane_tf_ops(plane) -> dict[str, str]:
    """``{op name: tf_op}`` of one plane's event metadata."""
    stat_names: dict[int, str] = {}
    events = []
    for f, v in fields(plane):
        if f == 5:
            meta = dict(fields(_map_values(v)))
            stat_names[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
        elif f == 4:
            events.append(_map_values(v))
    tf_op_id = next((k for k, name in stat_names.items() if name == TF_OP),
                    None)
    out = {}
    for event in events:
        name = value = None
        for f, v in fields(event):
            if f == 2:
                name = bytes(v).decode()
            elif f == 5 and tf_op_id is not None:
                stat = dict(fields(v))
                if stat.get(1) != tf_op_id:
                    continue
                if 5 in stat:
                    value = bytes(stat[5]).decode()
                elif 7 in stat:
                    value = stat_names.get(stat[7])
        if name is not None and value is not None:
            out[name] = value
    return out


def tf_ops(path: str) -> dict[str, str | None]:
    """``{op name: tf_op}`` over the file's TPU planes, the name being what
    ``ProfileData`` gives as the op event's name. An op without a
    ``tf_op`` (the copies XLA inserts) is absent; a name that carries two
    different ``tf_op`` values (two programs' ``%copy.3``) maps to None."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: dict[str, str | None] = {}
    for f, plane in fields(space):
        if f != 1:
            continue
        name = next((bytes(v).decode() for k, v in fields(plane) if k == 2),
                    "")
        if not DEVICE_PLANE.fullmatch(name):
            continue
        for op, value in _plane_tf_ops(plane).items():
            out[op] = value if out.get(op, value) == value else None
    return out
