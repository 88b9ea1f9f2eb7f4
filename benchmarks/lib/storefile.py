"""A replica's durable log read back from its FILE: the yardstick's own
parser of ``<store>/stable-store-replica<id>``.

Written from the record format's description and importing nothing of
the program, so that ``correct`` judges what is on the disk and not the
program's in-memory mirror of it. The file is the magic ``MPXL0002``
and then records of ``[type u8][payload bytes u32][crc32 u32][payload]``
(little endian; the crc covers the five header bytes and the payload).
A type-1 payload is a run of 34-byte slot rows; type 2 is the commit
frontier (i32); type 3 a snapshot of the applied table. A record that
runs past the end of the bytes given is a torn tail and is not there; a
record whose crc fails is not there either.

A later row of a slot supersedes an earlier one unless its ballot is
lower. ``rows`` is the contiguous prefix of slots from 0 — the log.
``first_end`` says, per command id, the file offset at which the first
record that holds it ends: the command is durable once that many bytes
of the file have been fsynced.
"""

from __future__ import annotations

import struct
import zlib

import numpy as np

MAGIC = b"MPXL0002"
REC_SLOTS, REC_FRONTIER, REC_SNAPSHOT = 1, 2, 3
SLOT_DT = np.dtype([
    ("inst", "<i4"), ("ballot", "<i4"), ("status", "u1"), ("op", "u1"),
    ("key", "<i8"), ("val", "<i8"), ("cmd_id", "<i4"), ("client_id", "<i4"),
])
_HDR = struct.Struct("<BII")  # type, payload bytes, crc


def parse(data: bytes) -> dict:
    """``rows`` (the log: slots 0..n-1, one row each), ``first_end``
    (``{cmd_id: offset}``), ``frontier``, and what was skipped."""
    if data[:len(MAGIC)] != MAGIC:
        raise ValueError("not a v2 stable store (bad magic)")
    pos, frontier, corrupt = len(MAGIC), -1, 0
    runs, ends = [], []
    while pos + _HDR.size <= len(data):
        rtype, plen, crc = _HDR.unpack_from(data, pos)
        body = pos + _HDR.size
        if body + plen > len(data):
            break  # torn tail: the record never reached the file whole
        end = body + plen
        if crc != zlib.crc32(data[body:end], zlib.crc32(data[pos:pos + 5])):
            corrupt += 1
        elif rtype == REC_SLOTS and plen % SLOT_DT.itemsize == 0:
            run = np.frombuffer(data, SLOT_DT, plen // SLOT_DT.itemsize, body)
            runs.append(run)
            ends.append(np.full(len(run), end, np.int64))
        elif rtype == REC_FRONTIER and plen == 4:
            frontier = max(frontier, struct.unpack_from("<i", data, body)[0])
        elif rtype == REC_SNAPSHOT:
            raise ValueError(
                "a snapshot record: the log below it is gone from the file "
                "and this comparison needs every row (keep -snap-every "
                "beyond what a run writes)")
        pos = end
    out = {"frontier": frontier, "corrupt_records": corrupt,
           "torn_bytes": len(data) - pos, "records": len(runs)}
    if not runs:
        return {**out, "rows": np.zeros(0, SLOT_DT), "first_end": {},
                "rows_past_a_hole": 0}
    rows, end = np.concatenate(runs), np.concatenate(ends)
    # first record end per client command (reversed: the first row
    # wins; no-op fills have a negative client id and no command)
    mine = rows["client_id"][::-1] >= 0
    first_end = dict(zip(rows["cmd_id"][::-1][mine].tolist(),
                         end[::-1][mine].tolist()))
    # per slot the last row among those of the highest ballot
    order = np.lexsort((np.arange(len(rows)), rows["ballot"], rows["inst"]))
    rows = rows[order]
    last = np.r_[rows["inst"][1:] != rows["inst"][:-1], True]
    rows = rows[last]
    gap = np.nonzero(rows["inst"] != np.arange(len(rows)))[0]
    n = int(gap[0]) if len(gap) else len(rows)
    return {**out, "rows": rows[:n], "first_end": first_end,
            "rows_past_a_hole": len(rows) - n}
