"""TPU RS kernels (bit-matrix matmul) vs the numpy GF(2^8) oracle."""

import jax.numpy as jnp
import numpy as np
import pytest

from chubaofs_tpu.ops import bitmatrix, gf256, rs


def test_mul_bit_matrix_matches_field(rng):
    for c in [0, 1, 2, 3, 0x1D, 0x80, 0xFF] + list(rng.integers(0, 256, 16)):
        mc = bitmatrix.mul_bit_matrix(int(c))
        d = rng.integers(0, 256, 64, dtype=np.uint8)
        bits = ((d[:, None] >> np.arange(8)) & 1).astype(np.uint8)  # (64, 8)
        out_bits = (bits @ mc.T) % 2
        packed = (out_bits << np.arange(8)).sum(axis=1).astype(np.uint8)
        assert np.array_equal(packed, gf256.gf_mul(np.uint8(c), d)), hex(int(c))


def test_unpack_pack_roundtrip_np(rng):
    x = rng.integers(0, 256, (5, 33), dtype=np.uint8)
    assert np.array_equal(bitmatrix.pack_bits_np(bitmatrix.unpack_bits_np(x)), x)


def test_unpack_pack_roundtrip_jax(rng):
    x = rng.integers(0, 256, (2, 5, 33), dtype=np.uint8)
    assert np.array_equal(np.asarray(rs.pack_bits(rs.unpack_bits(x))), x)


def test_expand_matrix_matches_gf_matmul(rng):
    a = rng.integers(0, 256, (4, 6), dtype=np.uint8)
    x = rng.integers(0, 256, (6, 100), dtype=np.uint8)
    want = gf256.gf_matmul(a, x)
    a_bits = bitmatrix.expand_matrix(a)
    x_bits = bitmatrix.unpack_bits_np(x)
    got = bitmatrix.pack_bits_np((a_bits.astype(np.int32) @ x_bits.astype(np.int32)) % 2)
    assert np.array_equal(got, want)


@pytest.mark.parametrize("n,m", [(3, 3), (6, 3), (12, 4), (15, 12)])
def test_kernel_encode_matches_oracle(rng, n, m):
    k = 257  # deliberately unaligned
    ker = rs.get_kernel(n, m)
    data = rng.integers(0, 256, (n, k), dtype=np.uint8)
    want = gf256.encode_numpy(ker.gen, data)
    got = np.asarray(ker.encode(data))
    assert np.array_equal(got, want)


def test_kernel_encode_batched(rng):
    ker = rs.get_kernel(6, 3)
    data = rng.integers(0, 256, (4, 6, 128), dtype=np.uint8)
    got = np.asarray(ker.encode(data))
    for b in range(4):
        want = gf256.encode_numpy(ker.gen, data[b])
        assert np.array_equal(got[b], want)


@pytest.mark.parametrize(
    "bad", [[0], [11], [15], [0, 1, 2, 3], [12, 13, 14, 15], [5, 11, 13, 15]]
)
def test_kernel_reconstruct(rng, bad):
    ker = rs.get_kernel(12, 4)
    data = rng.integers(0, 256, (12, 200), dtype=np.uint8)
    shards = np.asarray(ker.encode(data))
    broken = shards.copy()
    broken[np.asarray(bad), :] = 0
    fixed = np.asarray(ker.reconstruct(broken, bad))
    assert np.array_equal(fixed, shards), f"pattern {bad}"


def test_kernel_reconstruct_data_only(rng):
    ker = rs.get_kernel(6, 3)
    data = rng.integers(0, 256, (6, 96), dtype=np.uint8)
    shards = np.asarray(ker.encode(data))
    broken = shards.copy()
    broken[2, :] = 0
    broken[7, :] = 0
    fixed = np.asarray(ker.reconstruct(broken, [2, 7], data_only=True))
    assert np.array_equal(fixed[:6], data)
    assert np.all(fixed[7] == 0)


def test_kernel_reconstruct_batched(rng):
    ker = rs.get_kernel(6, 3)
    data = rng.integers(0, 256, (8, 6, 64), dtype=np.uint8)
    shards = np.asarray(ker.encode(data))
    broken = shards.copy()
    broken[:, [1, 4], :] = 0
    fixed = np.asarray(ker.reconstruct(broken, [1, 4]))
    assert np.array_equal(fixed, shards)


def test_kernel_too_many_missing():
    ker = rs.get_kernel(6, 3)
    with pytest.raises(ValueError):
        ker.repair_matrix([0, 1, 2, 3])


def test_kernel_verify(rng):
    ker = rs.get_kernel(6, 3)
    data = rng.integers(0, 256, (6, 64), dtype=np.uint8)
    shards = np.array(ker.encode(data))
    assert bool(ker.verify(shards))
    shards[7, 10] ^= 0xFF
    assert not bool(ker.verify(shards))


def test_verify_batched(rng):
    ker = rs.get_kernel(4, 2)
    data = rng.integers(0, 256, (3, 4, 32), dtype=np.uint8)
    shards = np.array(ker.encode(data))
    shards[1, 5, 0] ^= 1
    ok = np.asarray(ker.verify(shards))
    assert ok.tolist() == [True, False, True]


def test_fused_pallas_kernel_interpret(rng):
    """The fused Pallas kernel (interpret mode) matches the XLA lowering."""
    from chubaofs_tpu.ops import pallas_gf

    ker = rs.get_kernel(6, 3)
    data = rng.integers(0, 256, (2, 6, 384), dtype=np.uint8)
    want = np.asarray(rs.gf_matmul_bytes(ker.parity_bits, data))
    got = np.asarray(
        pallas_gf.gf_matmul_bytes_fused(
            ker.parity_bits, data, tile_k=128, interpret=True
        )
    )
    assert np.array_equal(got, want)


def test_plane_major_permutation_exact():
    """pm[b*r+p, b2*n+j] must equal bits[p*8+b, j*8+b2] elementwise."""
    from chubaofs_tpu.ops import bitmatrix, pallas_gf

    r, n = 2, 4
    bits = bitmatrix.expand_matrix(rs.get_kernel(n, r).gen[n:, :])
    pm = pallas_gf.plane_major(bits)
    assert pm.shape == bits.shape
    for b in range(8):
        for p in range(r):
            for b2 in range(8):
                for j in range(n):
                    assert pm[b * r + p, b2 * n + j] == bits[p * 8 + b, j * 8 + b2]


def test_pick_group_caps_and_divisibility():
    from chubaofs_tpu.ops import pallas_gf

    # EC(12,4): 32x96 bits -> g=4 fills exactly 128 rows
    assert pallas_gf.pick_group(16, 32, 96) == 4
    assert pallas_gf.pick_group(64, 16, 32) == 8  # EC(4,2), col cap 512 allows 8
    assert pallas_gf.pick_group(7, 32, 96) == 1  # prime batch: no divisor
    for b, r8, n8 in [(24, 24, 48), (64, 16, 32), (16, 32, 96), (8, 48, 160)]:
        g = pallas_gf.pick_group(b, r8, n8)
        assert b % g == 0 and g * r8 <= 128 and g * n8 <= 512


def test_group_stacked_math_matches_per_stripe(rng):
    """kron(I_g, mat) over the (b/g, g*n, k) view == per-stripe matmul."""
    ker = rs.get_kernel(6, 3)
    b, n, k = 8, 6, 256
    g = 4
    host = rng.integers(0, 256, (b, n, k), dtype=np.uint8)
    want = np.asarray(rs.gf_matmul_bytes(ker.parity_bits, host))
    mat_s = np.kron(np.eye(g, dtype=np.int8), ker.parity_bits)
    got = np.asarray(
        rs.gf_matmul_bytes(mat_s, host.reshape(b // g, g * n, k))
    ).reshape(b, 3, k)
    assert np.array_equal(got, want)


def test_fused_kernel_group_stacked_interpret(rng):
    """The Pallas kernel on group-stacked (wide) shapes matches the oracle."""
    from chubaofs_tpu.ops import pallas_gf

    ker = rs.get_kernel(6, 3)
    b, n, k = 4, 6, 384
    g = 4  # rows 4*24=96 <= 128
    host = rng.integers(0, 256, (b, n, k), dtype=np.uint8)
    want = np.asarray(rs.gf_matmul_bytes(ker.parity_bits, host))
    mat_s = np.kron(np.eye(g, dtype=np.int8), ker.parity_bits)
    got = np.asarray(
        pallas_gf.gf_matmul_bytes_fused(
            mat_s, host.reshape(b // g, g * n, k), tile_k=128, interpret=True
        )
    ).reshape(b, 3, k)
    assert np.array_equal(got, want)


def test_hostbatch_matches_dispatch(rng):
    """gf_matmul_hostbatch: host (..., n, k) in -> host (..., r, k), oracle-equal."""
    ker = rs.get_kernel(12, 4)
    host = rng.integers(0, 256, (6, 12, 200), dtype=np.uint8)
    want = np.asarray(rs.gf_matmul_bytes(ker.parity_bits, host))
    got = rs.gf_matmul_hostbatch(ker.parity_bits, host)
    assert isinstance(got, np.ndarray)
    assert np.array_equal(got, want)
    # repair matrix path (non-square, fewer rows)
    mat, present, missing = ker.repair_matrix([0, 5])
    from chubaofs_tpu.ops import bitmatrix

    mat_bits = bitmatrix.expand_matrix(mat).astype(np.int8)
    stripes = np.asarray(ker.encode(host))
    sur = stripes[:, present, :]
    rows = rs.gf_matmul_hostbatch(mat_bits, sur)
    assert np.array_equal(rows, stripes[:, missing, :])


def test_fused_kernel_empty_repair_matrix():
    """A repair plan with no missing rows must not crash the fused path."""
    from chubaofs_tpu.ops import pallas_gf

    ker = rs.get_kernel(6, 3)
    empty = np.zeros((0, 48), dtype=np.int8)
    out = pallas_gf.gf_matmul_bytes_fused(jnp.asarray(empty), np.zeros((6, 256), np.uint8))
    assert out.shape == (0, 256)
    # lost parity shard with data_only=True -> missing == [] -> no-op
    data = np.arange(6 * 256, dtype=np.uint8).reshape(6, 256)
    stripe = np.asarray(ker.encode(data))
    plan = ker.repair_plan([7], data_only=True)
    fixed = np.asarray(ker.apply_repair(plan, jnp.asarray(stripe)))
    assert np.array_equal(fixed, stripe)


def test_lowering_propagates_backend_errors(monkeypatch):
    """A backend that cannot initialise must surface, never read as "not a
    TPU": the old _use_fused() turned any exception into the CPU einsum."""
    import jax

    def broken():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    rs.lowering.cache_clear()
    monkeypatch.setattr(jax, "default_backend", broken)
    try:
        with pytest.raises(RuntimeError, match="Unable to initialize"):
            rs._use_fused()
        with pytest.raises(RuntimeError):
            rs.gf_matmul_hostbatch(rs.get_kernel(4, 2).parity_bits,
                                   np.zeros((1, 4, 128), np.uint8))
    finally:
        rs.lowering.cache_clear()


def test_lowering_decided_once_from_the_backend(monkeypatch):
    import jax

    rs.lowering.cache_clear()
    try:
        assert rs.lowering() == rs.EINSUM and not rs._use_fused()  # tests: cpu
        rs.lowering.cache_clear()
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert rs.lowering() == rs.FUSED and rs._use_fused()
        monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
        assert rs.lowering() == rs.FUSED  # once per process
    finally:
        rs.lowering.cache_clear()
