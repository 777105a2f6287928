"""The gateway's frame protocol, client side, for the load generators.

    frame := uint32_be(len(payload)) payload;  payload := JSON object

A copy of the framing the program's gateway speaks, so the load
generators import nothing of the program (and never JAX).  One
`Connection` carries one request at a time: an independent user.
"""

from __future__ import annotations

import asyncio
import hashlib
import itertools
import json
import struct
import time
from typing import Optional, Sequence, Tuple

HDR = struct.Struct(">I")


def content_key(text: str) -> str:
    """The store's address of a text: SHA-256 of its UTF-8 bytes."""
    return hashlib.sha256(text.encode("utf-8")).hexdigest()


def ids_digest(ids: Sequence[int]) -> str:
    """Digest of a token-id sequence as little-endian uint32 words."""
    try:
        return hashlib.sha1(struct.pack(f"<{len(ids)}I", *ids)).hexdigest()
    except (struct.error, TypeError):
        return "not uint32 ids"


class Connection:
    def __init__(self, reader: asyncio.StreamReader,
                 writer: asyncio.StreamWriter) -> None:
        self.reader = reader
        self.writer = writer
        self._ids = itertools.count(1)

    @classmethod
    async def open(cls, port: int, host: str = "127.0.0.1") -> "Connection":
        reader, writer = await asyncio.open_connection(
            host, port, limit=1 << 26)
        return cls(reader, writer)

    async def call(self, op: str, **fields) -> Tuple[Optional[dict], float, float]:
        """Send one request; returns (response or None if the connection
        died, send time, time of the last response byte)."""
        doc = {"op": op, "id": next(self._ids), **fields}
        payload = json.dumps(doc).encode("utf-8")
        sent = time.monotonic()
        try:
            self.writer.write(HDR.pack(len(payload)) + payload)
            await self.writer.drain()
            (length,) = HDR.unpack(await self.reader.readexactly(HDR.size))
            body = await self.reader.readexactly(length)
        except (asyncio.IncompleteReadError, ConnectionError, OSError):
            return None, sent, time.monotonic()
        done = time.monotonic()
        return json.loads(body), sent, done

    async def close(self) -> None:
        self.writer.close()
        try:
            await self.writer.wait_closed()
        except (ConnectionError, OSError):
            pass
