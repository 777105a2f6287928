"""The gateway's frame protocol, client side, for the load generators.

    frame := uint32_be(len(payload)) payload;  payload := JSON object

A copy of the framing the program's gateway speaks, so the load
generators import nothing of the program (and never JAX).  One
`Connection` carries one request at a time: an independent user.  One
`Pipeline` carries every request of an open-loop client as it falls
due, however many are still in flight, and matches each response to its
request by id, as the gateway allows.
"""

from __future__ import annotations

import asyncio
import hashlib
import itertools
import json
import struct
import time
from typing import Dict, Optional, Sequence, Tuple

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


class Pipeline:
    def __init__(self, reader: asyncio.StreamReader,
                 writer: asyncio.StreamWriter) -> None:
        self.reader = reader
        self.writer = writer
        self._ids = itertools.count(1)
        self._waiting: Dict[int, asyncio.Future] = {}
        self._reading = asyncio.ensure_future(self._read())

    @classmethod
    async def open(cls, port: int, host: str = "127.0.0.1") -> "Pipeline":
        reader, writer = await asyncio.open_connection(
            host, port, limit=1 << 26)
        return cls(reader, writer)

    async def _read(self) -> None:
        try:
            while True:
                (length,) = HDR.unpack(await self.reader.readexactly(HDR.size))
                body = await self.reader.readexactly(length)
                done = time.monotonic()
                doc = json.loads(body)
                fut = self._waiting.pop(doc.get("id"), None)
                if fut is not None and not fut.done():
                    fut.set_result((doc, done))
        except (asyncio.IncompleteReadError, ConnectionError, OSError):
            pass
        finally:
            for fut in self._waiting.values():
                if not fut.done():
                    fut.set_result((None, time.monotonic()))
            self._waiting.clear()

    async def call(self, op: str, deadline: float,
                   **fields) -> Tuple[Optional[dict], float, float]:
        """Send one request; returns (response, or None if the connection
        died or no answer came by ``deadline``, send time, time of the
        last response byte)."""
        rid = next(self._ids)
        payload = json.dumps({"op": op, "id": rid, **fields}).encode("utf-8")
        sent = time.monotonic()
        if self._reading.done():
            return None, sent, sent
        fut = asyncio.get_running_loop().create_future()
        self._waiting[rid] = fut
        try:
            self.writer.write(HDR.pack(len(payload)) + payload)
            await self.writer.drain()
            resp, done = await asyncio.wait_for(
                fut, max(0.0, deadline - time.monotonic()))
        except (asyncio.TimeoutError, ConnectionError, OSError):
            self._waiting.pop(rid, None)
            return None, sent, time.monotonic()
        return resp, sent, done

    async def close(self) -> None:
        self.writer.close()
        try:
            await self.writer.wait_closed()
        except (ConnectionError, OSError):
            pass
        self._reading.cancel()
        try:
            await self._reading
        except asyncio.CancelledError:
            pass
