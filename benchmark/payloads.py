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
    """memcmp of ``body`` against object (a, b) without building a copy."""
    if len(body) != size:
        return False
    base = pool[base_index(a, b, len(pool))]
    off = small_offset(a, b, size, len(base))
    mv = memoryview(body)
    return (bytes(mv[:STAMP_LEN]) == stamp(seed, a, b)
            and mv[STAMP_LEN:] == memoryview(base)[off + STAMP_LEN: off + size])
