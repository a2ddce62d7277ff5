"""The plain reference for an LRC local-stripe repair: the row a lost GLOBAL
stripe position held, solved from its own AZ's local stripe ALONE, and a count
of the reads a repair made across the AZ boundary. Numpy only, on
reference.py's field and generator and reference_decode.py's matrix inverse;
nothing of the program.

An LRC mode's second stage (reference.encode) is, in each AZ, the systematic
Cauchy code RS(local_n, local_m) over that AZ's data + global-parity shards
(reference.az_shards, local_n of them) with the AZ's local parities as its
parity rows. Seen from inside the AZ a lost global shard is therefore a lost
DATA row of that small code: invert the generator rows of any local_n
surviving rows of the local stripe and multiply. No shard of another AZ is
looked at, which is what the mode's local parities are bought for; it is
independent of reference_rebuild.rebuilt_row, which decodes the same row from
N global survivors of any AZ, and the two must agree (the tests hold it)."""

from __future__ import annotations

import numpy as np

import reference
import reference_decode


def geometry(mode: dict) -> tuple[int, int]:
    """(local_n, local_m) of an LRC mode's local stripes."""
    azs = mode["az_count"]
    return (mode["N"] + mode["M"]) // azs, mode["L"] // azs


def az_of(mode: dict, pos: int) -> int:
    """The AZ a stripe position is dealt to (contiguous dealing: data, then
    global parity, then local parity, each AZ by AZ)."""
    n, m, azs = mode["N"], mode["M"], mode["az_count"]
    if pos < n:
        return pos // (n // azs)
    if pos < n + m:
        return (pos - n) // (m // azs)
    return (pos - n - m) // (mode["L"] // azs)


def local_stripe(mode: dict, az: int) -> list[int]:
    """Stripe positions of one AZ's local stripe, in the local code's own
    order: its data + global-parity shards, then its local parities."""
    _, local_m = geometry(mode)
    first = mode["N"] + mode["M"] + az * local_m
    return reference.az_shards(mode, az) + list(range(first, first + local_m))


def local_rebuilt_row(shards: list, lost: int, mode: dict, code: dict) -> bytes:
    """The bytes of the GLOBAL stripe position ``lost`` from its AZ's local
    stripe alone. ``shards`` holds one entry a stripe position (bytes of any
    length: several stripes laid end to end are solved at once, the code
    being column-independent), None where nothing can be read; only the
    entries of ``lost``'s own AZ are looked at, and never the one at ``lost``.
    ValueError where the mode has no local stripe, ``lost`` is not a global
    position, or fewer than local_n rows of the AZ's stripe survive."""
    n, m = mode["N"], mode["M"]
    if not mode["L"] or not 0 <= lost < n + m:
        raise ValueError(f"position {lost} has no local stripe to be solved from")
    local_n, local_m = geometry(mode)
    idx = local_stripe(mode, az_of(mode, lost))
    present = [p for p, g in enumerate(idx) if g != lost and shards[g] is not None][:local_n]
    if len(present) < local_n:
        raise ValueError(f"only {len(present)} rows of the AZ's local stripe survive, need {local_n}")
    poly = int(code["field_poly"], 16)
    gen = np.concatenate([np.eye(local_n, dtype=np.uint8), reference.cauchy(local_n, local_m, poly)])
    # the lost row as ONE combination of the survivors: its generator row times
    # the inverse of theirs (local_n coefficients), then local_n table passes
    coef = reference.matmul(gen[[idx.index(lost)]], reference_decode.invert(gen[present], poly), poly)
    rows = np.stack([np.frombuffer(bytes(shards[idx[p]]), np.uint8) for p in present])
    return reference.matmul(coef, rows, poly)[0].tobytes()


def cross_az_reads(mode: dict, lost: int, read_positions) -> int:
    """How many of the stripe positions a repair of ``lost`` read lie in
    another AZ than ``lost``'s: 0 for a repair by the local stripe, about half
    of N for a global decode from the first N survivors in stripe order."""
    az = az_of(mode, lost)
    return sum(1 for p in read_positions if az_of(mode, p) != az)
