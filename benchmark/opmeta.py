"""What `jax.profiler.ProfileData` leaves out of an `.xplane.pb`: the
metadata of a device op.

`xplane.load` gives an op event its name and times.  The file holds
more for every op, in the *event metadata* its `metadata_id` points at
(an event's own stats are `device_offset_ps` and `device_duration_ps`
only): `tf_op`, which is JAX's `op_name` and so carries every
`jax.named_scope` (`dbcsr_tpu.core.timings.device_scope`) the op was
created under; `source`, its file:line; `bytes_accessed`, the
compiler's estimate.  They are there with `enable_hlo_proto = False`,
as the harness records.

This is a walk of the protobuf wire format in plain Python (no package
beyond the standard library is imported), for the few messages it needs
(tensorflow's `xplane_pb2` reads them too, takes 20 s to import and need
not be installed).  Field numbers, from tsl's `xplane.proto`:
  XSpace          planes=1
  XPlane          name=2 lines=3 event_metadata=4 stat_metadata=5
                  (both maps: entry key=1 value=2)
  XLine           name=2 timestamp_ns=3 events=4
  XEvent          metadata_id=1 offset_ps=2 duration_ps=3
  XEventMetadata  id=1 name=2 stats=5
  XStatMetadata   id=1 name=2
  XStat           metadata_id=1 uint64_value=3 int64_value=4
                  str_value=5 ref_value=7 (the id of a stat metadata
                  whose name is the string)
"""

from __future__ import annotations

from .xplane import DEVICE_PLANE, OPS_LINE

STATS = ("tf_op", "source", "bytes_accessed")


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
    """(field number, value) of one message: an int for a varint or a
    fixed-width field, a memoryview for a length-delimited one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val = buf[i:i + size]
            i += size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            val = int.from_bytes(buf[i:i + size], "little")
            i += size
        else:
            raise ValueError(f"wire type {wire} at byte {i}: not an xplane")
        yield key >> 3, val


def text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def first(buf, number: int, default=None):
    for num, val in fields(buf):
        if num == number:
            return val
    return default


def _map_values(entries):
    """The values of a protobuf map field's entries."""
    return [first(entry, 2) for entry in entries]


def _plane_ops(plane) -> list:
    lines, event_meta, stat_meta = [], [], []
    for num, val in fields(plane):
        if num == 3:
            lines.append(val)
        elif num == 4:
            event_meta.append(val)
        elif num == 5:
            stat_meta.append(val)
    stat_name = {}
    for meta in _map_values(stat_meta):
        entry = dict(fields(meta))
        stat_name[entry.get(1, 0)] = text(entry.get(2, b""))
    ops_of = {}  # event metadata id -> [name, tf_op, source, bytes_accessed]
    for meta in _map_values(event_meta):
        ident, name, stats = 0, "", {}
        for num, val in fields(meta):
            if num == 1:
                ident = val
            elif num == 2:
                name = text(val)
            elif num == 5:
                stat = dict(fields(val))
                key = stat_name.get(stat.get(1))
                if key not in STATS:
                    continue
                if 5 in stat:
                    stats[key] = text(stat[5])
                elif 7 in stat:
                    stats[key] = stat_name.get(stat[7], "")
                else:  # a count: never negative, so int64 reads as uint64
                    stats[key] = stat.get(3, stat.get(4, 0))
        ops_of[ident] = [name, stats.get("tf_op", ""),
                         stats.get("source", ""),
                         int(stats.get("bytes_accessed", 0))]
    out = []
    for line in lines:
        if text(first(line, 2, b"")) != OPS_LINE:
            continue
        t0_ns = first(line, 3, 0)
        for num, val in fields(line):
            if num != 4:
                continue
            ev = dict(fields(val))
            name, tf_op, source, nbytes = ops_of[ev.get(1, 0)]
            # whole ns, as ProfileData cuts them
            out.append([name, float(t0_ns + ev.get(2, 0) // 1000),
                        float(ev.get(3, 0) // 1000), tf_op, source, nbytes])
    return out


def device_ops(path: str) -> dict:
    """{device plane name: [[name, start_ns, dur_ns, tf_op, source,
    bytes_accessed], ...]}: the events of each device's `XLA Ops` line
    in file order, names and times as `xplane.load` gives them, the rest
    from the event's metadata ("" or 0 where the file has none).  An
    event finds its metadata through its `metadata_id`, never by name:
    two programs may both hold a `%fusion.1`."""
    with open(path, "rb") as fh:
        space = memoryview(fh.read())
    out = {}
    for num, plane in fields(space):
        if num != 1:
            continue
        name = text(first(plane, 2, b""))
        if DEVICE_PLANE.match(name):
            out[name] = _plane_ops(plane)
    return out
