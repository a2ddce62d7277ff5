"""The plain reference of the expiry cell: a dict-of-bytes store with the
system's three verbs, and what its live objects should leave on the blobnodes.
Written from the configuration file (policies, modes, code, record_framing)
and importing nothing of the program: numpy, the standard library and
reference.py (the stripe of a blob).

    put(token, bytes) / delete(token) / get(token) -> bytes | None (not-found)
    stripes(token)        -> [(N + M + L, shard) array] a blob, reference.encode's
    stored_bytes(size)    -> bytes an object of `size` holds in chunk datafiles:
                             every shard of every blob, each a record of
                             header + payload + one CRC a block
    live_stored_bytes()   -> that, summed over the objects not deleted"""

from __future__ import annotations

import numpy as np

import reference


def blob_sizes(size: int, max_blob: int) -> list[int]:
    return [min(max_blob, size - off) for off in range(0, size, max_blob)]


def mode_of(size: int, config: dict) -> dict:
    """The code mode the policy table gives an object of ``size`` bytes."""
    for p in config["policies"]:
        if p["min_size"] <= size and (p["max_size"] is None or size <= p["max_size"]):
            return config["modes"][p["mode"]]
    raise ValueError(f"no policy for an object of {size} bytes")


def record_bytes(payload: int, framing: dict) -> int:
    """A shard of ``payload`` bytes as a chunk datafile holds it."""
    blocks = -(-payload // framing["crc_block_bytes"])
    return framing["header_bytes"] + payload + framing["crc_bytes_per_block"] * blocks


def stored_bytes(size: int, config: dict) -> int:
    mode, framing = mode_of(size, config), config["record_framing"]
    shards = mode["N"] + mode["M"] + mode["L"]
    return sum(shards * record_bytes(
        reference.shard_size(b, mode["N"], config["code"]["min_shard_size"]), framing)
        for b in blob_sizes(size, config["max_blob_size"]))


class Store:
    """What the system must answer, operation by operation."""

    def __init__(self, config: dict):
        self.config = config
        self.objects: dict[str, bytes] = {}
        self.deleted: set[str] = set()

    def put(self, token: str, data: bytes) -> None:
        self.objects[token] = bytes(data)

    def delete(self, token: str) -> None:
        """Idempotent, and of a token never put: fire-and-ack."""
        self.objects.pop(token, None)
        self.deleted.add(token)

    def get(self, token: str) -> bytes | None:
        return self.objects.get(token)

    def stripes(self, token: str) -> list[np.ndarray]:
        data = self.objects[token]
        mode, out, off = mode_of(len(data), self.config), [], 0
        for b in blob_sizes(len(data), self.config["max_blob_size"]):
            out.append(reference.encode(data[off: off + b], mode, self.config["code"]))
            off += b
        return out

    def live_stored_bytes(self) -> int:
        return sum(stored_bytes(len(d), self.config) for d in self.objects.values())
