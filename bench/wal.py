"""Reads the broker's write-ahead journal from disk, as its format documents
it, to check that every changeset the run sent is there intact.

Segment files ``wal_<first-seq>.seg``: an 8-byte header (``RJNL`` + u32
version), then frames ``[u32 payload_len][u32 crc32(payload)][payload]``;
a payload is ``[u32 header_len][header JSON][array blobs]``, the header
naming the record's ``kind`` and its arrays as ``[name, dtype, shape]``.
"""
from __future__ import annotations

import json
import struct
import zlib
from pathlib import Path
from typing import Iterator, List, Sequence, Tuple

import numpy as np

_HEADER = 8


def ingests(directory: Path) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
    """The ``(removed, added)`` arrays of every intact ingest record, in
    order; stops at the first frame whose checksum fails."""
    segs = sorted(Path(directory).glob("wal_*.seg"),
                  key=lambda p: int(p.stem.split("_")[1]))
    for seg in segs:
        data = seg.read_bytes()
        if data[:4] != b"RJNL":
            return
        off = _HEADER
        while off + 8 <= len(data):
            length, crc = struct.unpack_from("<II", data, off)
            payload = data[off + 8: off + 8 + length]
            if len(payload) != length or zlib.crc32(payload) != crc:
                return
            off += 8 + length
            (hlen,) = struct.unpack_from("<I", payload, 0)
            head = json.loads(payload[4:4 + hlen])
            if head.get("kind") != "ingest":
                continue
            pos = 4 + hlen
            arrays = {}
            for name, dt, shape in head["arrays"]:
                dtype = np.dtype(dt)
                n = int(np.prod(shape, dtype=np.int64))
                arrays[name] = np.frombuffer(
                    payload, dtype, n, pos).reshape(shape)
                pos += n * dtype.itemsize
            empty = np.zeros((0, 3), np.int32)
            yield arrays.get("removed", empty), arrays.get("added", empty)


def _same(x: np.ndarray, y: np.ndarray) -> bool:
    x = np.unique(np.asarray(x).reshape(-1, 3), axis=0)
    y = np.unique(np.asarray(y).reshape(-1, 3), axis=0)
    return x.shape == y.shape and bool((x == y).all())


def missing_ingests(directory: Path,
                    sent: Sequence[Tuple[np.ndarray, np.ndarray]]) -> int:
    """How many of the changesets sent, in order, the journal lacks."""
    got: List[Tuple[np.ndarray, np.ndarray]] = list(ingests(directory))
    missing = 0
    for i, (d, a) in enumerate(sent):
        if i >= len(got) or not (_same(got[i][0], d) and _same(got[i][1], a)):
            missing += 1
    return missing
