"""The plain reference: GF(2^8) erasure coding in numpy, written from the code
definition in the configuration file and importing nothing of the program.

Field: GF(2^8) over the primitive polynomial named in the configuration
(0x11d). Code: systematic, the parity block is the Cauchy matrix
C[i][j] = 1 / ((n + i) xor j). An LRC mode is encoded in two stages, as upstream
does (lrcencoder.go): global parity over the N data shards first, then in each
AZ local parity over that AZ's data + global-parity shards with the Cauchy
code of the local geometry. Shard order in a stripe: N data, M global parity,
L local parity (AZ by AZ); shards are dealt to AZs contiguously."""

from __future__ import annotations

import numpy as np


def tables(poly: int) -> tuple[np.ndarray, np.ndarray]:
    exp = np.zeros(510, np.uint8)
    log = np.zeros(256, np.int64)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= poly
    exp[255:] = exp[:255]
    return exp, log


def mul_table(poly: int) -> np.ndarray:
    """256 x 256 products."""
    exp, log = tables(poly)
    t = exp[(log[:, None] + log[None, :]) % 255].astype(np.uint8)
    t[0, :] = 0
    t[:, 0] = 0
    return t


def cauchy(n: int, m: int, poly: int) -> np.ndarray:
    """(m, n) parity block: C[i][j] = inverse of ((n + i) xor j)."""
    exp, log = tables(poly)
    out = np.zeros((m, n), np.uint8)
    for i in range(m):
        for j in range(n):
            out[i, j] = exp[(255 - log[(n + i) ^ j]) % 255]
    return out


def matmul(mat: np.ndarray, rows: np.ndarray, poly: int) -> np.ndarray:
    """(r, n) GF matrix times (n, k) byte rows -> (r, k), row by row."""
    t = mul_table(poly)
    out = np.zeros((mat.shape[0], rows.shape[1]), np.uint8)
    for r in range(mat.shape[0]):
        for c in range(mat.shape[1]):
            out[r] ^= t[mat[r, c]][rows[c]]
    return out


def shard_size(blob_size: int, n: int, min_shard: int) -> int:
    return max(-(-blob_size // n), min_shard)


def split(blob: bytes, n: int, min_shard: int) -> np.ndarray:
    """A blob as its (n, shard_size) data shards, zero-padded at the tail."""
    k = shard_size(len(blob), n, min_shard)
    flat = np.zeros(n * k, np.uint8)
    flat[: len(blob)] = np.frombuffer(blob, np.uint8)
    return flat.reshape(n, k)


def az_shards(mode: dict, az: int) -> list[int]:
    """Stripe indexes of the data + global-parity shards dealt to one AZ."""
    n, m, azs = mode["N"], mode["M"], mode["az_count"]
    dn, pn = n // azs, m // azs
    return list(range(az * dn, (az + 1) * dn)) + list(range(n + az * pn, n + (az + 1) * pn))


def encode(blob: bytes, mode: dict, code: dict) -> np.ndarray:
    """The whole stripe of ``blob`` under ``mode`` (N, M, L, az_count):
    (N + M + L, shard_size) bytes."""
    poly, n, m, l = int(code["field_poly"], 16), mode["N"], mode["M"], mode["L"]
    data = split(blob, n, code["min_shard_size"])
    stripe = np.concatenate([data, matmul(cauchy(n, m, poly), data, poly)])
    if l:
        azs = mode["az_count"]
        local_n, local_m = (n + m) // azs, l // azs
        lmat = cauchy(local_n, local_m, poly)
        stripe = np.concatenate(
            [stripe] + [matmul(lmat, stripe[az_shards(mode, az)], poly) for az in range(azs)])
    return stripe
