"""The op_name of each device op in a profiler trace, read off the wire.

``jax.profiler.ProfileData`` gives an op event's name (its HLO text,
``%fusion.504 = u32[...] fusion(...), ...``) but not its metadata, where
the op's ``op_name`` (the ``jax.named_scope`` path it was traced under,
``jit(_program)/join/radix_sort/while/body/gather``) sits. That is a
stat of the event's entry in its plane's ``event_metadata`` map, named
``tf_op`` and written ``<op_name>:<op type>``; a fusion carries its root
op's. This reader walks the ``.xplane.pb`` protobuf (``XSpace``) by hand
and reads only that map and the stats' names, skipping each plane's
``lines`` (the events, nearly all of the file) by their length.

Wire layout read (tensorflow/tsl/profiler/protobuf/xplane.proto):
``XSpace.planes`` 1; ``XPlane`` name 2, lines 3, event_metadata 4 (map
entry: key 1, value 2), stat_metadata 5 (same); ``XEventMetadata`` name
2, stats 5; ``XStat`` metadata_id 1, str_value 5, ref_value 7 (a
stat_metadata id whose name is the value); ``XStatMetadata`` name 2.
"""
from __future__ import annotations

OP_NAME_STAT = "tf_op"

_VARINT, _I64, _LEN, _I32 = 0, 1, 2, 5


def _varint(buf, i: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def fields(buf, start: int = 0, end: int | None = None):
    """(field number, wire type, value) of each field of the message in
    ``buf[start:end]``; a length-delimited value is its (start, end)."""
    end = len(buf) if end is None else end
    i = start
    while i < end:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == _VARINT:
            value, i = _varint(buf, i)
        elif wire == _LEN:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire == _I64:
            value, i = bytes(buf[i:i + 8]), i + 8
        elif wire == _I32:
            value, i = bytes(buf[i:i + 4]), i + 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield field, wire, value


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_entry(buf, span) -> tuple[int, tuple[int, int] | None]:
    key, value = 0, None
    for f, _, v in fields(buf, *span):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _event_metadata(buf, span):
    """(name, [(stat metadata id, str value or None, ref or None)])."""
    name, stats = "", []
    for f, _, v in fields(buf, *span):
        if f == 2:
            name = _text(buf, v)
        elif f == 5:
            sid, text, ref = 0, None, None
            for sf, _, sv in fields(buf, *v):
                if sf == 1:
                    sid = sv
                elif sf == 5:
                    text = _text(buf, sv)
                elif sf == 7:
                    ref = sv
            stats.append((sid, text, ref))
    return name, stats


def op_names(raw: bytes, plane_prefix: str = "/device:") -> dict:
    """For each plane whose name starts with ``plane_prefix``: its name ->
    ``{event name: set of op_names}``. An event name with more than one
    op_name is ambiguous (``ambiguous``); ops without the stat map to
    the empty set."""
    buf = memoryview(raw)
    out = {}
    for f, _, plane in fields(buf):
        if f != 1:
            continue
        name, metas, stat_names = None, [], {}
        for pf, _, pv in fields(buf, *plane):
            if pf == 2:
                name = _text(buf, pv)
                if not name.startswith(plane_prefix):
                    break
            elif pf == 4:
                metas.append(pv)
            elif pf == 5:
                key, value = _map_entry(buf, pv)
                for sf, _, sv in fields(buf, *value) if value else ():
                    if sf == 2:
                        stat_names[key] = _text(buf, sv)
        if name is None or not name.startswith(plane_prefix):
            continue
        names: dict[str, set] = {}
        for entry in metas:
            _, value = _map_entry(buf, entry)
            if value is None:
                continue
            ev_name, stats = _event_metadata(buf, value)
            ops = names.setdefault(ev_name, set())
            for sid, text, ref in stats:
                if stat_names.get(sid) != OP_NAME_STAT:
                    continue
                if text is None and ref is not None:
                    text = stat_names.get(ref)
                if text:
                    ops.add(text.rsplit(":", 1)[0] if ":" in text else text)
        out[name] = names
    return out


def ambiguous(names: dict) -> list[str]:
    """The event names of one plane that map to more than one op_name."""
    return sorted(n for n, ops in names.items() if len(ops) > 1)
