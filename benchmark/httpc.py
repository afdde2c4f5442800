"""A small asyncio HTTP/1.0 client for the load generator: one
connection per request (the served surface is http.server's HTTP/1.0,
which closes after each response), so thousands of requests in flight
cost one event loop and no threads."""
from __future__ import annotations

import asyncio
from typing import Dict, Optional, Tuple
from urllib.parse import urlsplit

Response = Tuple[int, Dict[str, str], bytes]


def split(url: str) -> Tuple[str, int]:
    u = urlsplit(url)
    return u.hostname or "127.0.0.1", int(u.port or 80)


async def request(addr: Tuple[str, int], method: str, path: str,
                  body: bytes = b"", headers: Optional[Dict[str, str]] = None,
                  timeout: float = 60.0) -> Response:
    """One request; returns (status, lower-cased headers, body).  Raises
    OSError / asyncio.TimeoutError / ValueError on transport failure."""
    return await asyncio.wait_for(
        _request(addr, method, path, body, headers or {}), timeout)


async def _request(addr, method, path, body, headers) -> Response:
    reader, writer = await asyncio.open_connection(*addr)
    try:
        head = [f"{method} {path} HTTP/1.0", f"Host: {addr[0]}:{addr[1]}",
                f"Content-Length: {len(body)}"]
        head += [f"{k}: {v}" for k, v in headers.items()]
        writer.write(("\r\n".join(head) + "\r\n\r\n").encode() + body)
        await writer.drain()
        raw = await reader.read()
    finally:
        writer.close()
    sep = raw.find(b"\r\n\r\n")
    if sep < 0:
        raise ValueError(f"truncated response ({len(raw)} bytes)")
    lines = raw[:sep].decode("latin-1").split("\r\n")
    status = int(lines[0].split()[1])
    hdrs = {}
    for line in lines[1:]:
        k, _, v = line.partition(":")
        hdrs[k.strip().lower()] = v.strip()
    return status, hdrs, raw[sep + 4:]
