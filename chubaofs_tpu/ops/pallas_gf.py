"""Fused Pallas kernel for the GF(2^8) bit-matrix product.

The plain XLA lowering (ops/rs.gf_matmul_bytes) materializes the 8x bit expansion
of the data in HBM (int8 bits in, int32 accumulator out), so encode throughput is
bandwidth-bound at ~an order of magnitude more HBM traffic than the payload. This
kernel keeps the whole unpack -> int8 MXU matmul -> parity-mask -> pack sequence in
VMEM: HBM sees only the uint8 payload in and the uint8 result out.

Layout choice (measured on v5e-1): the GF(2) matrix is stored PLANE-MAJOR — row
b*r+p is output-bit b of GF-row p, column b*n+j is input-bit b of GF-column j — so
the in-kernel unpack is eight scalar shifts producing whole bit-planes and the pack
is eight plane slices OR-ed together. The byte-major order (row p*8+b) used by
ops/bitmatrix would need (n, 8, kt) -> (8n, kt) sublane reshapes inside the kernel,
which cost more VPU time than the matmul itself. Mosaic constraints baked in here:
no 8/16-bit vector shifts (unpack runs in int32), no in-kernel bitwidth-changing
bitcast, iota only in 16/32 bit (avoided entirely).

Reference counterpart: the amd64 assembly loops of klauspost/reedsolomon (the only
"math kernel" in the reference, SURVEY §2.3) — this is its TPU replacement.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BITS = 8
# measured on v5e-1 (slope-timed): 128KiB tiles edge out 32KiB (~54.4 vs
# ~53.4 GB/s encode) — fewer grid steps amortize per-tile overhead while the
# (12+4)x128KiB working set still double-buffers in VMEM
DEFAULT_TILE_K = 131072
# per-grid-step in+out block budget for the adaptive tile choice: ~2 MiB is
# the measured sweet spot at every stacking factor (G=1:128K, G=2:64K,
# G=4:32K tiles all sit on (n+r)*kt ~= 2 MiB and all beat their neighbours)
TILE_BYTES = 2 << 20


def _perm(dim: int) -> list[int]:
    """plane-major index b*dim+i -> byte-major index i*8+b, for one axis."""
    return [(i % dim) * BITS + i // dim for i in range(dim * BITS)]


def plane_major(mat_bits: np.ndarray) -> np.ndarray:
    """Permute a byte-major (8r, 8n) GF(2) matrix to the kernel's plane-major order."""
    r8, n8 = mat_bits.shape
    return np.asarray(mat_bits)[_perm(r8 // BITS)][:, _perm(n8 // BITS)]


def pick_group(b: int, r8: int, n8: int, cap: int | None = None) -> int:
    """Largest divisor g of the batch with g*r8 <= 128 and g*n8 <= 512.

    ``cap`` additionally bounds g (e.g. a dp-sharded caller passes b//dp so
    grouping never collapses the batch below the mesh's data-parallel axis).

    Block-diagonal generator stacking (PERF.md "paths past 100"): the stationary
    matrix of one EC(12,4) stripe is 32x96 on a 128x128 systolic array (~19%
    utilized). Stacking g stripes' generators block-diagonally (kron(I_g, mat))
    and viewing g stripes as one wide (g*n, k) stripe fills the MXU rows —
    measured on v5e-1: EC(12,4) encode 54 -> ~130 GB/s at g=4 (rows=128).
    Beyond 128 rows (a second row-tile) throughput regresses, hence the cap.

    The grouping MUST happen at the host boundary ((b, n, k) -> (b/g, g*n, k)
    is a free numpy view there): on device the same reshape physically
    rearranges the sublane-tiled HBM buffer (measured 131 -> 53 GB/s fed
    through an in-jit reshape), and every in-kernel merge variant (4D block +
    VMEM reshape, per-slab unpack + concat, slab-loop matmul accumulation)
    defeats Mosaic's streaming fusion and blows the 16M scoped-VMEM limit.
    rs.group_stack packages the host-side transform.
    """
    best = 1
    hi = min(b, 128) if cap is None else min(b, 128, cap)
    for g in range(2, hi + 1):
        if g * r8 > 128 or g * n8 > 512:
            break
        if b % g == 0:
            best = g
    return best


def _gf_kernel(mat_ref, data_ref, out_ref):
    """One (batch, k-tile) grid step: out = (mat @ bits(data)) mod 2, packed.

    mat_ref:  (8r, 8n) int8, plane-major — resident in VMEM for all grid steps
    data_ref: (1, n, kt) uint8
    out_ref:  (1, r, kt) uint8
    """
    r = out_ref.shape[1]
    data32 = data_ref[0].astype(jnp.int32)  # Mosaic has no 8-bit vector shifts
    planes = [((data32 >> b) & 1).astype(jnp.int8) for b in range(BITS)]
    bits = jnp.concatenate(planes, axis=0)  # (8n, kt), plane-major

    acc = jax.lax.dot_general(
        mat_ref[...],
        bits,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )  # (8r, kt) int32, plane-major rows
    packed = acc[0:r] & 1
    for b in range(1, BITS):
        packed |= (acc[b * r : (b + 1) * r] & 1) << b
    out_ref[0] = packed.astype(jnp.uint8)


def gf_matmul_bytes_fused(
    mat_bits: jax.Array,
    shards: jax.Array,
    tile_k: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Drop-in fused equivalent of rs.gf_matmul_bytes.

    mat_bits: (8r, 8n) int8 in the standard byte-major order; shards:
    (..., n, k) uint8 -> (..., r, k) uint8. k is padded to the tile size
    internally and sliced back.

    Host numpy matrices (the rs.py contract: generator and repair matrices
    stay numpy) are permuted to the kernel's plane-major layout in numpy at
    trace time; traced/device matrices (e.g. repair plans fed as runtime args
    through shard_map) pay a tiny in-graph gather instead — one compiled
    program keeps serving every repair pattern with no recompilation.

    For MXU-filling batched throughput, feed GROUP-STACKED operands (see
    rs.group_stack / pick_group): a (8gr, 8gn) block-diagonal matrix over
    (b/g, g*n, k) host-viewed stripes.
    """
    if isinstance(mat_bits, np.ndarray):
        # numpy at trace time: the device never sees the permutation
        mat_pm = plane_major(mat_bits).astype(np.int8)
    else:
        rows, cols = (jnp.asarray(_perm(d // BITS), jnp.int32) for d in mat_bits.shape)
        mat_pm = mat_bits[rows][:, cols]
    return gf_matmul_planes(mat_pm, shards, tile_k, interpret)


def gf_matmul_planes(
    mat_pm: jax.Array,
    shards: jax.Array,
    tile_k: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """gf_matmul_bytes_fused for a matrix ALREADY in the kernel's plane-major
    order (plane_major): what rs.MatrixPlan keeps resident on the device, so a
    launch neither permutes nor ships it. The one call site of the jitted core:
    a numpy and a resident operand run the same compiled program."""
    r8, n8 = mat_pm.shape
    r, n = r8 // BITS, n8 // BITS
    lead = shards.shape[:-2]
    k = shards.shape[-1]
    assert shards.shape[-2] == n, (shards.shape, mat_pm.shape)
    if r8 == 0 or k == 0:
        return jnp.zeros((*lead, r, k), jnp.uint8)

    b = 1
    for d in lead:
        b *= d

    out = _fused_core(mat_pm, shards.reshape(b, n, k), tile_k=tile_k, interpret=interpret)
    return out.reshape(*lead, r, k)


@functools.partial(jax.jit, static_argnames=("tile_k", "interpret"))
def _fused_core(
    mat_pm: jax.Array,
    data: jax.Array,
    tile_k: int | None,
    interpret: bool,
) -> jax.Array:
    """Jitted core: (b, n, k) uint8 -> (b, r, k) uint8 via the Pallas kernel.

    mat_pm is already in the kernel's plane-major layout.
    """
    b, n, k = data.shape
    r8, n8 = mat_pm.shape
    r = r8 // BITS

    if tile_k is None:
        # keep the per-step in+out block near TILE_BYTES: measured sweet spot
        # at every matrix width ((12+4)x128K, (24+8)x64K, (48+16)x32K all win)
        tile_k = max(128, min(DEFAULT_TILE_K, TILE_BYTES // (n + r) // 128 * 128))

    # Mosaic pads sub-tile sublane counts up to full int8 tiles (32 sublanes),
    # so with few shard rows the unpack intermediates cost ~8*32 bytes/column
    # regardless of n and the scoped-VMEM stack blows the 16M limit at large
    # tiles (measured: n=3, r=1 at kt=128K needs 30.8M). Narrow tiles keep the
    # stack bounded; wide (possibly group-stacked) stripes keep larger tiles.
    if min(n, r) < 8:
        tile_k = min(tile_k, 32768)

    # pick the tile so the grid divides evenly with minimal padding: distribute
    # the 128-aligned length over ceil(k/tile_k) tiles (pad <= 128 * n_tiles
    # instead of up to a full tile)
    k128 = -(-k // 128) * 128
    n_tiles = max(1, -(-k128 // tile_k))
    kt = -(-k128 // n_tiles // 128) * 128
    kp = kt * n_tiles
    if kp != k:
        data = jnp.pad(data, ((0, 0), (0, 0), (0, kp - k)))

    out = pl.pallas_call(
        _gf_kernel,
        grid=(b, kp // kt),
        in_specs=[
            pl.BlockSpec((r8, n8), lambda i, j: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, n, kt), lambda i, j: (i, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, r, kt), lambda i, j: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((b, r, kp), jnp.uint8),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
        interpret=interpret,
    )(mat_pm, data)

    if kp != k:
        out = out[..., :k]
    return out
