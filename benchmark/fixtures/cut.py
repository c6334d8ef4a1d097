"""Cut a recorded `.xplane.pb` to what a fixture needs and gzip it:

    python benchmark/fixtures/cut.py <in.xplane.pb> <out.xplane.pb.gz>

keeps the device planes and the host's (a trace of the tiny filtered
cell is 2.7 MB, two thirds of it `/host:metadata`; cut and compressed
it is 160 KB).  Planes are copied byte for byte.
"""

from __future__ import annotations

import gzip
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import opmeta, xplane  # noqa: E402


def _varint(value: int) -> bytes:
    out = bytearray()
    while value >= 0x80:
        out.append(value & 0x7F | 0x80)
        value >>= 7
    out.append(value)
    return bytes(out)


def cut(data: bytes) -> bytes:
    out = bytearray()
    for num, plane in opmeta.fields(memoryview(data)):
        if num != 1:
            continue
        name = opmeta.text(opmeta.first(plane, 2, b""))
        if name == xplane.HOST_PLANE or xplane.DEVICE_PLANE.match(name):
            out += b"\x0a" + _varint(len(plane)) + bytes(plane)
    return bytes(out)


if __name__ == "__main__":
    with open(sys.argv[1], "rb") as fh:
        kept = cut(fh.read())
    with gzip.GzipFile(sys.argv[2], "wb", mtime=0) as fh:
        fh.write(kept)
    print(f"{len(kept)} bytes kept, {os.path.getsize(sys.argv[2])} written")
