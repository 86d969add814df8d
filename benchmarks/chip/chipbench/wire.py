"""The serving plane's wire protocol, client side, written from its
specification so that the front end's codec is part of what is measured
and checked.

A frame is a 5-byte header ``struct('>IB')`` (body length, codec id)
and a body; codec 1 is msgpack.  An ndarray travels as
``{"__nd__": {"dtype", "shape", "data"}}`` with raw bytes.  Requests
carry an ``op`` and a client-chosen ``id`` that the reply echoes;
replies to ``submit`` may come out of order.
"""

from __future__ import annotations

import asyncio
import struct

import msgpack
import numpy as np

HEADER = struct.Struct(">IB")
CODEC_MSGPACK = 1


def _pack(obj):
    if isinstance(obj, np.ndarray):
        return {"__nd__": {"dtype": str(obj.dtype), "shape": list(obj.shape),
                           "data": obj.tobytes()}}
    if isinstance(obj, dict):
        return {k: _pack(v) for k, v in obj.items()}
    return obj


def _unpack(obj):
    if isinstance(obj, dict):
        nd = obj.get("__nd__")
        if nd is not None:
            arr = np.frombuffer(nd["data"], dtype=np.dtype(nd["dtype"]))
            return arr.reshape(nd["shape"])
        return {k: _unpack(v) for k, v in obj.items()}
    if isinstance(obj, list):
        return [_unpack(v) for v in obj]
    return obj


def encode(msg: dict) -> bytes:
    body = msgpack.packb(_pack(msg), use_bin_type=True)
    return HEADER.pack(len(body), CODEC_MSGPACK) + body


class Connection:
    """One TCP connection with pipelined, id-correlated calls.

    ``on_reply`` (optional) is called with ``(id, reply, t_recv)`` on
    the event loop as each reply is read, before the caller's future
    resolves, so the receive time is taken at the frame's arrival.
    """

    def __init__(self, host: str, port: int):
        self.host, self.port = host, port
        self._reader = self._writer = None
        self._waiters: dict = {}
        self._next_id = 0
        self._reads = None

    async def open(self) -> None:
        self._reader, self._writer = await asyncio.open_connection(
            self.host, self.port)
        self._reads = asyncio.get_running_loop().create_task(self._read())

    async def close(self) -> None:
        if self._reads is not None:
            self._reads.cancel()
            try:
                await self._reads
            except asyncio.CancelledError:
                pass
        if self._writer is not None:
            self._writer.close()
            try:
                await self._writer.wait_closed()
            except (ConnectionResetError, BrokenPipeError):
                pass

    async def _read(self) -> None:
        loop = asyncio.get_running_loop()
        try:
            while True:
                head = await self._reader.readexactly(HEADER.size)
                length, codec = HEADER.unpack(head)
                if codec != CODEC_MSGPACK:
                    raise ValueError(f"reply in codec {codec}, sent msgpack")
                body = await self._reader.readexactly(length)
                t = loop.time()
                msg = _unpack(msgpack.unpackb(body, raw=False))
                fut = self._waiters.pop(msg.get("id"), None)
                if fut is not None and not fut.done():
                    fut.set_result((msg, t))
        except (asyncio.IncompleteReadError, ConnectionResetError):
            for fut in self._waiters.values():
                if not fut.done():
                    fut.set_exception(ConnectionError("connection closed"))
            self._waiters.clear()

    def send(self, msg: dict) -> asyncio.Future:
        """Write one request now; the future resolves to (reply, t_recv)
        with ``t_recv`` on the event loop's clock."""
        self._next_id += 1
        fut = asyncio.get_running_loop().create_future()
        self._waiters[self._next_id] = fut
        self._writer.write(encode(dict(msg, id=self._next_id)))
        return fut

    async def call(self, msg: dict) -> dict:
        reply, _ = await self.send(msg)
        if not reply.get("ok"):
            raise RuntimeError(f"{msg.get('op')} failed: {reply}")
        return reply
