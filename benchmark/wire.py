"""The access gateway's wire calls, from the standard library alone (copied in
shape from chubaofs_tpu.blobstore.gateway.AccessClient: PUT /put with the
object as the body answers a JSON Location token; POST /get with
{"location", "offset", "size"} answers the bytes). One keep-alive connection
per Client; a Client belongs to one thread. Never imports jax or the program."""

from __future__ import annotations

import http.client
import json


class WireError(Exception):
    pass


class Client:
    def __init__(self, addr: str, timeout: float = 120.0):
        host, _, port = addr.rpartition(":")
        self._host, self._port, self._timeout = host, int(port), timeout
        self._conn: http.client.HTTPConnection | None = None

    def _request(self, method: str, path: str, body) -> tuple[int, bytes]:
        # no resend: PUT /put is not idempotent (a resend stores the object
        # twice), so a broken connection is a failed op
        if self._conn is None:
            self._conn = http.client.HTTPConnection(self._host, self._port, timeout=self._timeout)
        try:
            self._conn.request(method, path, body=body,
                               headers={"Content-Type": "application/octet-stream"})
            resp = self._conn.getresponse()
            return resp.status, resp.read()
        except (OSError, http.client.HTTPException) as e:
            self.close()
            raise WireError(f"{method} {path}: {type(e).__name__}: {e}") from None

    def put(self, data) -> str:
        """Store ``data``; returns the Location token (a JSON string)."""
        status, body = self._request("PUT", "/put", data)
        if status != 200:
            raise WireError(f"put -> {status} {body[:200]!r}")
        return body.decode()

    def get(self, location: str) -> bytes:
        req = json.dumps({"location": location, "offset": 0, "size": -1}).encode()
        status, body = self._request("POST", "/get", req)
        if status != 200:
            raise WireError(f"get -> {status} {body[:200]!r}")
        return body

    def close(self) -> None:
        if self._conn is not None:
            self._conn.close()
            self._conn = None
