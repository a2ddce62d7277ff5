"""reference.py at the 2-AZ production geometry (EC16P20L2: N 16, M 20, L 2),
which no earlier test held it to: any 16 of the 36 global shards give the data
back, and each AZ's local parity is the Cauchy(18, 1) code over that AZ's
8 data + 10 global-parity shards."""
import json
import os

import numpy as np
import pytest

import reference

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(BENCH, "configs", "az2-ec16p20l2.json")) as f:
    CONFIG = json.load(f)
CODE = CONFIG["code"]
POLY = int(CODE["field_poly"], 16)


def gf_solve(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """x with a @ x = b over GF(2^8): Gauss-Jordan, written from the field's
    tables alone (a: (n, n), b: (n, k))."""
    mul = reference.mul_table(POLY)
    exp, log = reference.tables(POLY)
    a, b = a.copy(), b.copy()
    n = len(a)
    for col in range(n):
        piv = next(r for r in range(col, n) if a[r, col])
        a[[col, piv]], b[[col, piv]] = a[[piv, col]], b[[piv, col]]
        inv = exp[(255 - log[a[col, col]]) % 255]
        a[col], b[col] = mul[inv][a[col]], mul[inv][b[col]]
        for r in range(n):
            if r != col and a[r, col]:
                f = a[r, col]
                a[r] ^= mul[f][a[col]]
                b[r] ^= mul[f][b[col]]
    return b


def stripe_of(mode_name: str, size: int, seed: int):
    mode = CONFIG["modes"][mode_name]
    blob = np.random.default_rng(seed).bytes(size)
    return mode, blob, reference.encode(blob, mode, CODE)


@pytest.mark.parametrize("mode_name,size", [("EC16P20L2", 16 * 2048 + 5), ("EC6P10L2", 6 * 3000 - 1)])
def test_stripe_shape_and_systematic_data(mode_name, size):
    mode, blob, stripe = stripe_of(mode_name, size, 3)
    n, total = mode["N"], mode["N"] + mode["M"] + mode["L"]
    k = max(-(-size // n), CODE["min_shard_size"])
    assert stripe.shape == (total, k)
    assert stripe[:n].tobytes()[:size] == blob and not stripe[:n].reshape(-1)[size:].any()


@pytest.mark.parametrize("seed", range(6))
def test_any_16_of_the_36_global_shards_decode_to_the_data(seed):
    mode, blob, stripe = stripe_of("EC16P20L2", 16 * 2048, seed)
    n, m = mode["N"], mode["M"]
    gen = np.concatenate([np.eye(n, dtype=np.uint8), reference.cauchy(n, m, POLY)])
    rng = np.random.default_rng([seed, 16, 36])
    keep = np.sort(rng.choice(n + m, size=n, replace=False))
    if seed == 0:
        keep = np.arange(n, n + n)  # no data shard at all: parities 0..15
    if seed == 1:  # what a whole AZ down leaves: AZ 1's 8 data + 10 parities, 16 of them
        keep = np.array(reference.az_shards(mode, 1)[:n])
    data = gf_solve(gen[keep], stripe[keep])
    assert data.tobytes() == blob


@pytest.mark.parametrize("mode_name", ["EC16P20L2", "EC6P10L2"])
def test_local_parity_is_cauchy_over_the_az_data_and_global_parity(mode_name):
    mode, _, stripe = stripe_of(mode_name, 40_000, 9)
    n, m, azs = mode["N"], mode["M"], mode["az_count"]
    local_n = (n + m) // azs
    lmat = reference.cauchy(local_n, 1, POLY)
    assert lmat.shape == (1, local_n)
    for az in range(azs):
        idx = reference.az_shards(mode, az)
        assert idx == list(range(az * n // azs, (az + 1) * n // azs)) + \
            list(range(n + az * m // azs, n + (az + 1) * m // azs))
        assert np.array_equal(stripe[n + m + az], reference.matmul(lmat, stripe[idx], POLY)[0])
        # one lost shard of the AZ comes back from the other 17 + the local parity
        lost = idx[3]
        rest = [i for i in idx if i != lost]
        col = lmat[0, idx.index(lost)]
        acc = stripe[n + m + az] ^ reference.matmul(lmat[:, [idx.index(i) for i in rest]], stripe[rest], POLY)[0]
        assert np.array_equal(gf_solve(np.array([[col]], np.uint8), acc[None])[0], stripe[lost])
