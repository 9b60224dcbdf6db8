"""The per-op metadata of an ``.xplane.pb`` that ``jax.profiler.
ProfileData`` does not hand out: each device op's XLA category.

XLA writes ``hlo_category`` ("convolution fusion", "loop fusion", "data
formatting", ...) as a stat of the op's *event metadata*; ``ProfileData``
exposes only the stats of the events themselves. This reads just the two
metadata tables of each plane straight from the protobuf wire format
(tsl/profiler/protobuf/xplane.proto: XSpace.planes=1; XPlane.name=2,
.event_metadata=4, .stat_metadata=5; map entries key=1, value=2;
XEventMetadata.id=1, .name=2, .stats=5; XStatMetadata.id=1, .name=2;
XStat.metadata_id=1, .str_value=5, .ref_value=7) and skips the events,
which ``ProfileData`` reads faster.
"""


def _varint(buf, i):
    shift = result = 0
    while True:
        b = buf[i]
        i += 1
        result |= (b & 0x7F) << shift
        if not b & 0x80:
            return result, i
        shift += 7


def _fields(buf, start=0, end=None):
    """Yield ``(field number, wire type, value)``; a length-delimited
    value is a ``(start, end)`` pair into ``buf``."""
    i = start
    end = len(buf) if end is None else end
    while i < end:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            val = (i, i + n)
            i += n
        elif wire == 1:
            val, i = buf[i:i + 8], i + 8
        elif wire == 5:
            val, i = buf[i:i + 4], i + 4
        else:
            raise ValueError("unsupported wire type %d" % wire)
        yield num, wire, val


def _text(buf, span):
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_entry(buf, span):
    key = value = None
    for num, wire, val in _fields(buf, *span):
        if num == 1 and wire == 0:
            key = val
        elif num == 2 and wire == 2:
            value = val
    return key, value


def categories(path, stat_name="hlo_category"):
    """``{plane name: {op name: category}}`` for the planes that carry
    the stat."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out = {}
    for num, wire, plane in _fields(buf):
        if num != 1 or wire != 2:
            continue
        name, events, stats = "", [], {}
        for pnum, pwire, val in _fields(buf, *plane):
            if pwire != 2:
                continue
            if pnum == 2:
                name = _text(buf, val)
            elif pnum == 4:
                events.append(_map_entry(buf, val)[1])
            elif pnum == 5:
                sid, span = _map_entry(buf, val)
                for snum, swire, sval in _fields(buf, *span):
                    if snum == 2 and swire == 2:
                        stats[sid] = _text(buf, sval)
        wanted = {sid for sid, sname in stats.items() if sname == stat_name}
        if not wanted:
            continue
        table = {}
        for span in events:
            if span is None:
                continue
            op, cat = None, None
            for enum, ewire, val in _fields(buf, *span):
                if enum == 2 and ewire == 2:
                    op = _text(buf, val)
                elif enum == 5 and ewire == 2:
                    sid = text = ref = None
                    for snum, swire, sval in _fields(buf, *val):
                        if snum == 1 and swire == 0:
                            sid = sval
                        elif snum == 5 and swire == 2:
                            text = _text(buf, sval)
                        elif snum == 7 and swire == 0:
                            ref = sval
                    if sid in wanted:
                        cat = text if text is not None else stats.get(ref)
            if op is not None and cat is not None:
                table[op] = cat
        out[name] = table
    return out
