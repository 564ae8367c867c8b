"""Write-ahead log (reference: pkg/vm/engine/tae/logstore + logservice —
redesigned: a single CRC-framed append log on the fileservice; the
Raft-replicated multi-shard variant slots in behind `append`/`replay` when
multi-host lands).

Frame: MAGIC u32len u32crc payload. Payload = JSON header + optional Arrow
IPC blob (insert batches travel as Arrow, not JSON).
"""

from __future__ import annotations

import json
import struct
import zlib
from typing import Dict, Iterator, Optional, Tuple

import numpy as np
import pyarrow as pa

from matrixone_tpu.storage import arrowio
from matrixone_tpu.storage.fileservice import FileService

_FRAME_MAGIC = 0x4D4F5741  # 'MOWA'


class WalWriter:
    def __init__(self, fs: FileService, path: str = "wal/wal.log"):
        self.fs = fs
        self.path = path

    def append(self, header: dict, arrow_blob: bytes = b"") -> None:
        from matrixone_tpu.utils.fault import INJECTOR
        from matrixone_tpu.utils import motrace, san
        if INJECTOR.trigger("wal.append") == "fail":
            raise IOError("fault injected: wal.append failed")
        hj = json.dumps(header).encode()
        payload = struct.pack("<I", len(hj)) + hj + arrow_blob
        frame = struct.pack("<III", _FRAME_MAGIC, len(payload),
                            zlib.crc32(payload)) + payload
        # WAL-then-apply under one commit critical section IS the commit
        # protocol — exempt the durable append like the quorum client
        with san.allow_blocking("wal.append under the commit lock is "
                                "the commit protocol"), \
                motrace.span("wal.sync", bytes=len(frame)):
            self.fs.append(self.path, frame)

    def truncate(self) -> None:
        # atomic-replace truncation: Engine._checkpoint_locked calls this
        # ONLY after the checkpoint manifest is durably renamed — a crash
        # between the two replays the tail against the OLD manifest (the
        # mocrash sweep's checkpoint-window drill pins the ordering)
        self.fs.write(self.path, b"")

    def replay(self, stats: Optional[dict] = None
               ) -> Iterator[Tuple[dict, bytes]]:
        return replay(self.fs, self.path, stats=stats)


def replay(fs: FileService, path: str = "wal/wal.log",
           stats: Optional[dict] = None) -> Iterator[Tuple[dict, bytes]]:
    """Yield (header, arrow_blob) for each intact frame; stops at the first
    torn/corrupt frame (crash-consistent tail handling).  `stats`, when
    given, is filled as the scan proceeds — at exhaustion it holds the
    recovery summary Engine.open reports: frames replayed, torn-tail
    bytes discarded (anything after the last intact frame), total log
    bytes."""
    if stats is None:
        stats = {}
    stats.update(frames=0, torn_bytes=0, bytes=0)
    if not fs.exists(path):
        return
    blob = fs.read(path)
    stats["bytes"] = len(blob)
    off = 0
    while off + 12 <= len(blob):
        magic, plen, crc = struct.unpack_from("<III", blob, off)
        if magic != _FRAME_MAGIC or off + 12 + plen > len(blob):
            break
        payload = blob[off + 12:off + 12 + plen]
        if zlib.crc32(payload) != crc:
            break
        (hlen,) = struct.unpack_from("<I", payload, 0)
        header = json.loads(payload[4:4 + hlen].decode())
        stats["frames"] += 1
        yield header, payload[4 + hlen:]
        off += 12 + plen
    stats["torn_bytes"] = len(blob) - off


def arrays_to_arrow(arrays, validity):
    """arrays values may be numpy arrays OR python lists of str/None
    (varchar columns travel as strings so WAL replay can re-encode them
    into the table dictionary — codes alone would go stale)."""
    return arrowio.arrays_to_ipc(arrays, validity)


def arrow_to_arrays(blob: bytes):
    """Inverse of arrays_to_arrow; string columns come back as python
    lists (str/None)."""
    return arrowio.ipc_to_arrays(blob)
