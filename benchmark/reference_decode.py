"""The plain reference for a degraded read: given the shards of a stripe that
can still be read, solve for the data rows by Gauss-Jordan elimination over
GF(2^8). Numpy only, built on reference.py's field and generator; nothing of
the program.

A global shard at stripe position p is row p of the generator [I; C] (identity
over the Cauchy block reference.cauchy gives) times the N data rows. Any N such
rows of a Cauchy code are independent, so N surviving global shards determine
the data: invert the N x N matrix of their generator rows and multiply. Local
parities (positions N + M and up) are a second stage over one AZ's shards and
are not used: a read that has N global survivors needs none of them."""

from __future__ import annotations

import numpy as np

import reference


def generator(mode: dict, code: dict) -> np.ndarray:
    """(N + M, N): identity over the Cauchy parity block."""
    n, m = mode["N"], mode["M"]
    return np.concatenate([np.eye(n, dtype=np.uint8),
                           reference.cauchy(n, m, int(code["field_poly"], 16))])


def invert(a: np.ndarray, poly: int) -> np.ndarray:
    """Inverse of a square GF(2^8) matrix by Gauss-Jordan; ValueError if it
    has none."""
    exp, log = reference.tables(poly)
    mul = reference.mul_table(poly)
    n = a.shape[0]
    work = np.concatenate([a.astype(np.uint8), np.eye(n, dtype=np.uint8)], axis=1)
    for col in range(n):
        pivot = next((r for r in range(col, n) if work[r, col]), None)
        if pivot is None:
            raise ValueError("the survivors' generator rows are not independent")
        work[[col, pivot]] = work[[pivot, col]]
        work[col] = mul[exp[(255 - log[work[col, col]]) % 255]][work[col]]
        for r in range(n):
            if r != col and work[r, col]:
                work[r] ^= mul[work[r, col]][work[col]]
    return work[:, n:]


def solve(present: list[int], survivors: np.ndarray, want: list[int], mode: dict,
          code: dict) -> np.ndarray:
    """The stripe rows ``want`` (global positions, data or parity) from the N
    survivors at global positions ``present`` (rows of ``survivors`` in that
    order): (len(want), k) bytes."""
    n, poly = mode["N"], int(code["field_poly"], 16)
    if len(present) != n or max(present) >= n + mode["M"]:
        raise ValueError(f"want {n} global survivors, got positions {present}")
    gen = generator(mode, code)
    data = reference.matmul(invert(gen[present], poly), np.asarray(survivors, np.uint8), poly)
    return reference.matmul(gen[want], data, poly)


def decode(shards: list, blob_size: int, mode: dict, code: dict) -> bytes:
    """The blob's bytes from a stripe with positions missing: ``shards`` holds
    one entry a stripe position, None where nothing can be read. Decodes from
    the first N global positions present."""
    n = mode["N"]
    present = [p for p, s in enumerate(shards[: n + mode["M"]]) if s is not None][:n]
    if len(present) < n:
        raise ValueError(f"only {len(present)} global shards present, need {n}")
    survivors = np.stack([np.frombuffer(bytes(shards[p]), np.uint8) for p in present])
    data = solve(present, survivors, list(range(n)), mode, code)
    return data.reshape(-1)[:blob_size].tobytes()
