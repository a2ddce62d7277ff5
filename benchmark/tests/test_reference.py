"""reference.py against a stripe worked by hand, and its LRC stage order."""
import numpy as np

import reference

CODE = {"field_poly": "0x11d", "min_shard_size": 1}


def test_ec_2_1_by_hand():
    # Cauchy row for n=2, m=1: [1/(2^0), 1/(2^1)] = [1/2, 1/3] = [0x8e, 0xf4] over 0x11d
    # (2 * 0x8e = 0x11c = 0x11d ^ 1; 3 * 0xf4 = 0xf4 ^ 0x1e8 = 0x11c likewise).
    assert reference.cauchy(2, 1, 0x11D).tolist() == [[0x8E, 0xF4]]
    blob = bytes([0x01, 0x02, 0x53, 0xFF, 0x03, 0x10, 0xCA, 0x80])
    stripe = reference.encode(blob, {"N": 2, "M": 1, "L": 0, "az_count": 1}, CODE)
    assert stripe[0].tolist() == [0x01, 0x02, 0x53, 0xFF]
    assert stripe[1].tolist() == [0x03, 0x10, 0xCA, 0x80]
    # 0x8e*d0 ^ 0xf4*d1 by shift-and-reduce on paper, e.g. byte 1: 0x8e*0x02 = 0x01, 0xf4*0x10 = 0xfb
    assert stripe[2].tolist() == [0x8F, 0xFA, 0xE1, 0x7A]


def test_tail_is_zero_padded_and_min_shard_holds():
    stripe = reference.encode(b"\x07" * 5, {"N": 3, "M": 1, "L": 0, "az_count": 1},
                              {"field_poly": "0x11d", "min_shard_size": 4})
    assert stripe.shape == (4, 4)
    assert stripe[:3].reshape(-1).tolist() == [7] * 5 + [0] * 7


def test_lrc_local_parity_is_over_each_az_data_and_global_parity():
    mode = {"N": 6, "M": 3, "L": 3, "az_count": 3}
    blob = np.random.default_rng(1).bytes(6 * 64)
    stripe = reference.encode(blob, mode, CODE)
    assert stripe.shape == (12, 64)
    lmat = reference.cauchy(3, 1, 0x11D)
    for az in range(3):
        rows = stripe[[2 * az, 2 * az + 1, 6 + az]]
        assert np.array_equal(stripe[9 + az], reference.matmul(lmat, rows, 0x11D)[0])
