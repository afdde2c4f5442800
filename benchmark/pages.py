"""Client-side op pages for ``POST /ingest/page``: the columnar wire
format of ``crdt_tpu/ingest/wire.py`` (version 1), encoded here so the
client depends on the published format and not on the program's code.

    magic b"CRDTPAGE" | u16 version=1 | u16 flags=0 | i32 origin |
    u32 page_seq | u32 n_ops | u32 key-table bytes | u32 value-table bytes |
    u32 crc32(rest) | u32[n] seq | i32[n] ts (-1 = stamp at admission) |
    u32[n] key id | u32[n] value id | key table | value table

A table is u32 count, u32[count] end offsets, then the UTF-8 bytes.
"""
from __future__ import annotations

import struct
import zlib
from typing import List, Sequence

import numpy as np

_HEADER = struct.Struct("<8sHHiIIIII")
TS_NOW = -1


def _table(strings: Sequence[str]) -> bytes:
    blobs = [s.encode("utf-8") for s in strings]
    ends = np.cumsum([len(b) for b in blobs], dtype=np.uint64).astype(np.uint32)
    return struct.pack("<I", len(blobs)) + ends.tobytes() + b"".join(blobs)


def encode(origin: int, page_seq: int, seq0: int, keys: List[str],
           values: List[str]) -> bytes:
    """One page of ``len(keys)`` single-key writes, every op stamped by
    the admitting node; op seqs run from ``seq0``.  Keys and values are
    each given once per op (the tables hold one entry per op)."""
    n = len(keys)
    ids = np.arange(n, dtype=np.uint32)
    body = (np.arange(seq0, seq0 + n, dtype=np.uint32).tobytes()
            + np.full(n, TS_NOW, np.int32).tobytes()
            + ids.tobytes() + ids.tobytes())
    kt, vt = _table(keys), _table(values)
    payload = body + kt + vt
    return _HEADER.pack(b"CRDTPAGE", 1, 0, origin, page_seq, n, len(kt),
                        len(vt), zlib.crc32(payload)) + payload
