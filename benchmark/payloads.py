"""Seeded payloads, shared by the generator child and the harness's verifier.
Imports numpy only — the generator process must never load jax.

Every object is a slice of a seeded random base buffer with a 16-byte stamp at
its head naming (seed, a, b), so any two objects differ and the verifier can
rebuild the exact bytes of any acknowledged object from its op record."""

from __future__ import annotations

import struct

import numpy as np

STAMP_LEN = 16
N_BASES = 4


def stamp(seed: int, a: int, b: int) -> bytes:
    return struct.pack("<QII", seed & 0xFFFFFFFFFFFFFFFF, a & 0xFFFFFFFF, b & 0xFFFFFFFF)


def bases(seed: int, size: int, n: int = N_BASES) -> list[bytes]:
    """n seeded random buffers of ``size`` bytes (the payload pool)."""
    return [np.random.default_rng([seed, 0xB45E, i]).bytes(size) for i in range(n)]


def base_index(a: int, b: int, n: int = N_BASES) -> int:
    return (a * 5 + b) % n


def small_offset(a: int, b: int, size: int, base_size: int) -> int:
    """Where a small object's bytes start inside a base buffer."""
    span = base_size - size
    return ((a * 2654435761 + b * 40503) % (span + 1)) if span > 0 else 0


def payload(pool: list[bytes], seed: int, a: int, b: int, size: int) -> bytes:
    """The exact bytes of object (a, b) of ``size`` bytes."""
    base = pool[base_index(a, b, len(pool))]
    off = small_offset(a, b, size, len(base))
    return stamp(seed, a, b) + base[off + STAMP_LEN: off + size]


def matches(body, pool: list[bytes], seed: int, a: int, b: int, size: int) -> bool:
    """Is ``body`` exactly object (a, b)? Its length, its 16-byte stamp, then
    EVERY remaining byte against the pool slice, in one memcmp: bytes.startswith
    over [start, end) of the base takes any buffer as its prefix and compares
    in C, so neither the body nor the base is copied, and with the lengths equal
    a match of the prefix is a match of every byte. ``body`` is whatever holds
    bytes: the ``bytes`` wire.Client.get returns, a bytearray, a memoryview.
    (Not ``memoryview == memoryview``: that unpacks element by element under the
    interpreter lock, 48.6 ms a 16 MiB body on the chip's host against 1.4, and
    caps a GET cell at its own generator.)"""
    mv = memoryview(body).cast("B")
    if mv.nbytes != size:
        return False
    base = pool[base_index(a, b, len(pool))]
    off = small_offset(a, b, size, len(base))
    return (mv[:STAMP_LEN] == stamp(seed, a, b)
            and base.startswith(mv[STAMP_LEN:], off + STAMP_LEN, off + size))
