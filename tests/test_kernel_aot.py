"""AOT-compile the served kernels for a TPU v5e with the real Mosaic/libtpu
compiler and no device: `get_topology_desc` describes the chip, `.lower()
.compile()` runs the whole pipeline up to the executable. A kernel the
compiler refuses (tiling, scoped-VMEM) fails here, in tier-1, instead of on
the first chip run. Numerics are chip_smoke.py's job — nothing executes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from chubaofs_tpu.codec.codemode import CodeMode, get_tactic
from chubaofs_tpu.codec.encoder import lrc_parity_matrix
from chubaofs_tpu.codec.service import bucket_len
from chubaofs_tpu.models import EC4P2_1M, EC6P3_4M, EC12P4_8M, EC20P4L2_16M
from chubaofs_tpu.ops import bitmatrix, pallas_gf, rs
from chubaofs_tpu.parallel import codec_mesh, sharded_gf_matmul


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu in this installation
        print(f"SKIP test_kernel_aot: cannot describe a v5e topology: {e!r}")
        pytest.skip(f"cannot describe a v5e topology: {e!r}")


def _tactic_bits(t) -> np.ndarray:
    if t.L:
        return bitmatrix.expand_matrix(lrc_parity_matrix(t)).astype(np.int8)
    return rs.get_kernel(t.N, t.M).parity_bits


def _encode_bits(model) -> np.ndarray:
    return _tactic_bits(model.tactic)


def _repair_bits(model, missing) -> np.ndarray:
    t = model.tactic
    return rs.get_kernel(t.N, t.M).repair_plan(list(missing))[0]


# the five BASELINE.json configs: (name, byte-major bit matrix, stripe model,
# stripes per drained batch as bench.py sizes them)
WIDTHS = [
    ("ec4p2-1mib-encode", _encode_bits(EC4P2_1M), EC4P2_1M, 64),
    ("ec6p3-4mib-encode", _encode_bits(EC6P3_4M), EC6P3_4M, 24),
    ("ec12p4-8mib-reconstruct-1miss", _repair_bits(EC12P4_8M, [0]),
     EC12P4_8M, 16),
    ("ec12p4-8mib-repair-3miss", _repair_bits(EC12P4_8M, [0, 5, 12]),
     EC12P4_8M, 64),
    ("ec20p4l2-16mib-encode", _encode_bits(EC20P4L2_16M), EC20P4L2_16M, 8),
]


@pytest.mark.parametrize("stacked", [False, True], ids=["g1", "stacked"])
@pytest.mark.parametrize("name,mat_bits,model,batch", WIDTHS,
                         ids=[w[0] for w in WIDTHS])
def test_fused_kernel_compiles_for_v5e(topo, name, mat_bits, model, batch,
                                       stacked):
    """Both programs that exist for a width: the matrix as a RUNTIME argument
    (how CodecService reaches the kernel, via rs.gf_matmul_hostbatch) and as
    a compile-time constant (how bench.py closes over it)."""
    n = model.tactic.N
    kb = bucket_len(model.shard_len)  # the service pads shards to the bucket
    g = pallas_gf.pick_group(batch, *mat_bits.shape) if stacked else 1
    if stacked:
        assert g > 1, f"{name}: batch {batch} does not stack"
    mat_s = np.kron(np.eye(g, dtype=np.int8), mat_bits)
    dev = SingleDeviceSharding(topo.devices[0])
    data = jax.ShapeDtypeStruct((batch // g, g * n, kb), jnp.uint8,
                                sharding=dev)
    mat = jax.ShapeDtypeStruct(mat_s.shape, jnp.int8, sharding=dev)

    runtime = pallas_gf._fused_core.lower(mat, data, tile_k=None,
                                          interpret=False).compile()
    assert "tpu_custom_call" in runtime.as_text()  # Mosaic, not an einsum
    const = jax.jit(
        lambda s: pallas_gf.gf_matmul_bytes_fused(mat_s, s)
    ).lower(data).compile()
    assert "tpu_custom_call" in const.as_text()


# the 2-AZ deployment's served shapes (benchmark/configs/az2-ec16p20l2.json):
# (name, mode, blob bytes, jobs in the drained batch). EC16P20L2's composed
# generator is 176 x 128 bits, past pick_group's 128-row cap: g = 1 at every
# batch count, and a second MXU row tile.
AZ2_SERVED = [
    ("ec16p20l2-4mib-b1", CodeMode.EC16P20L2, 4 << 20, 1),
    ("ec16p20l2-4mib-b32", CodeMode.EC16P20L2, 4 << 20, 32),
    ("ec6p10l2-64kib-b8", CodeMode.EC6P10L2, 64 << 10, 8),
    ("ec6p10l2-1mib-b8", CodeMode.EC6P10L2, 1 << 20, 8),
]


@pytest.mark.parametrize("name,mode,blob,batch", AZ2_SERVED,
                         ids=[w[0] for w in AZ2_SERVED])
def test_az2_served_shapes_compile_for_v5e(topo, name, mode, blob, batch):
    """The program rs.gf_matmul_hostbatch launches for a drained batch of the
    2-AZ LRC modes: shards padded to the service's bucket, no group stacking
    (both matrices pass 128 bit-rows at g = 2), the matrix a runtime argument."""
    t = get_tactic(mode)
    mat_bits = _tactic_bits(t)
    assert mat_bits.shape == (8 * (t.M + t.L), 8 * t.N)
    kb = bucket_len(t.shard_size(blob))
    assert pallas_gf.pick_group(batch, *mat_bits.shape) == 1  # no stacking
    dev = SingleDeviceSharding(topo.devices[0])
    data = jax.ShapeDtypeStruct((batch, t.N, kb), jnp.uint8, sharding=dev)
    mat = jax.ShapeDtypeStruct(mat_bits.shape, jnp.int8, sharding=dev)
    compiled = pallas_gf._fused_core.lower(mat, data, tile_k=None,
                                           interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


# the AZ-down cell's decode (benchmark/configs/az2-ec16p20l2-azdown.json): 8
# rows wanted from 16 survivors, 64 x 128 bits a job over 262,144 columns; an
# even batch count stacks two jobs (128 x 256), an odd one stays at g = 1.
# The cell's streams keep at most 8 x pipeline_window 3 = 24 jobs queued.
@pytest.mark.parametrize("batch,g", [(1, 1), (2, 2), (23, 1), (24, 2)])
def test_azdown_decode_shapes_compile_for_v5e(topo, batch, g):
    t = get_tactic(CodeMode.EC16P20L2)
    live = [i for i in range(t.N + t.M) if t.az_of_shard(i) == 1]
    mat = rs.get_kernel(t.N, t.M).window_matrix(live[: t.N], list(range(t.N // 2)))
    mat_bits = bitmatrix.expand_matrix(mat).astype(np.int8)
    assert pallas_gf.pick_group(batch, *mat_bits.shape) == g
    mat_s = np.kron(np.eye(g, dtype=np.int8), mat_bits)  # what rs.group_stack launches
    assert mat_s.shape == (g * 64, g * 128)
    kb = bucket_len(t.shard_size(4 << 20))
    assert kb == 262144
    dev = SingleDeviceSharding(topo.devices[0])
    data = jax.ShapeDtypeStruct((batch // g, g * t.N, kb), jnp.uint8, sharding=dev)
    mat_arg = jax.ShapeDtypeStruct(mat_s.shape, jnp.int8, sharding=dev)
    compiled = pallas_gf._fused_core.lower(mat_arg, data, tile_k=None,
                                           interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_gf_matmul_compiles_on_a_dp4_mesh(topo):
    """CodecService(mesh=...)'s step under shard_map over the four chips of
    a v5e 2x2 host: EC(12,4) group-stacked, matrix replicated at run time."""
    mesh = codec_mesh(topo.devices, dp=4, sp=1)
    run = sharded_gf_matmul(mesh)
    assert run.lowering == rs.FUSED  # keyed off the mesh's platform
    mat_bits = _encode_bits(EC12P4_8M)
    g = pallas_gf.pick_group(16, *mat_bits.shape, cap=16 // 4)
    mat = jax.ShapeDtypeStruct((g * mat_bits.shape[0], g * mat_bits.shape[1]),
                               jnp.int8, sharding=NamedSharding(mesh, P()))
    data = jax.ShapeDtypeStruct(
        (16 // g, g * 12, bucket_len(EC12P4_8M.shard_len)), jnp.uint8,
        sharding=NamedSharding(mesh, P("dp", None, "sp")))
    with mesh:
        compiled = run.jitted.lower(mat, data).compile()
    assert "tpu_custom_call" in compiled.as_text()
