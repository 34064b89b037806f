"""Per-op metadata of a profiler trace (``.xplane.pb``) that
``jax.profiler.ProfileData`` leaves out.

On the TPU each device op's event metadata carries stats beside its name
(the HLO text that ``ProfileData`` gives as the event's name): among them
``tf_op``, the op's ``op_name``, i.e. the path of ``jax.named_scope``s and
transforms it was traced under (``jit(job)/while/body/fed_sync/...:``).
``ProfileData`` exposes only the events' own stats, so this module reads
the file's protobuf wire format directly (the ``XSpace`` messages of
``xplane.proto``), with no dependency beyond the standard library.
"""
from __future__ import annotations

DEVICE_PLANES = "/device:"

# Field numbers of xplane.proto.
SPACE_PLANES = 1
PLANE_NAME, PLANE_EVENT_METADATA, PLANE_STAT_METADATA = 2, 4, 5
META_NAME, META_STATS = 2, 5                    # XEventMetadata
STAT_METADATA_ID, STAT_STR, STAT_REF = 1, 5, 7  # XStat
STATMETA_ID, STATMETA_NAME = 1, 2               # XStatMetadata
MAP_VALUE = 2                                   # map entry's value
TF_OP = "tf_op"


def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf):
    """``(field number, value)`` of each field of one message: an int for
    varints and fixed-width values, a memoryview for length-delimited ones."""
    i, end = 0, len(buf)
    while i < end:
        tag, i = _varint(buf, i)
        wire = tag & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = int.from_bytes(buf[i:i + size], "little"), i + size
        else:
            raise ValueError(f"xplane: unsupported wire type {wire}")
        yield tag >> 3, value


def _text(value) -> str:
    return bytes(value).decode("utf-8", "replace")


def _plane_tf_ops(plane) -> dict:
    """``{event-metadata name: tf_op or None}`` of one plane. A name whose
    entries disagree (two programs, one text) maps to None: ambiguous."""
    entries, stat_names = [], {}
    for num, value in fields(plane):
        if num == PLANE_EVENT_METADATA:
            entries.append(value)
        elif num == PLANE_STAT_METADATA:
            meta = dict(fields(dict(fields(value))[MAP_VALUE]))
            stat_names[meta.get(STATMETA_ID, 0)] = _text(
                meta.get(STATMETA_NAME, b""))
    tf_id = next((k for k, v in stat_names.items() if v == TF_OP), None)
    out = {}
    for entry in entries:
        name, tf_op = None, None
        for num, value in fields(dict(fields(entry))[MAP_VALUE]):
            if num == META_NAME:
                name = _text(value)
            elif num == META_STATS and tf_id is not None:
                stat = dict(fields(value))
                if stat.get(STAT_METADATA_ID) == tf_id:
                    if STAT_STR in stat:
                        tf_op = _text(stat[STAT_STR])
                    elif STAT_REF in stat:
                        tf_op = stat_names.get(stat[STAT_REF])
        if name is None:
            continue
        out[name] = tf_op if out.get(name, tf_op) == tf_op else None
    return out


def tf_ops(path: str) -> dict:
    """``{plane name: {op name: tf_op or None}}`` of the device planes. Other
    planes (the host's, often the largest) are skipped undecoded."""
    with open(path, "rb") as f:
        data = memoryview(f.read())
    out = {}
    for num, plane in fields(data):
        if num != SPACE_PLANES:
            continue
        name = next((_text(v) for n, v in fields(plane) if n == PLANE_NAME),
                    "")
        if name.startswith(DEVICE_PLANES):
            out[name] = _plane_tf_ops(plane)
    return out
