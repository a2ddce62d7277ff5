"""Reed-Solomon encode/reconstruct as MXU bit-matrix products (the TPU hot loop).

Reference counterpart: klauspost/reedsolomon's Encode/Reconstruct SIMD loops behind
CubeFS's ec.Encoder (reference blobstore/common/ec/encoder.go:41-151). Here both
operations are ONE primitive: a GF(2) matrix product

    out_bits = (M_bits @ shard_bits) mod 2

executed as an int8 matmul on the MXU with int32 accumulation and a parity mask.
Encode uses the generator's parity block for M; reconstruct uses rows of
gen[missing] @ inv(gen[survivors]) computed on the host in numpy (tiny, O(n^3) on
n<=36 matrices) and shipped to the device as a runtime argument — so ONE compiled
kernel per shape serves every encode, decode, and repair pattern, with no
recompilation when the set of missing shards changes.

Batching: all kernels take (..., n, k) with arbitrary leading batch dims; the
scheduler's bulk-repair path stacks thousands of stripes into one call
(reference analog: blobstore/scheduler migrate batches, SURVEY §3.5).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chubaofs_tpu import chaos
from chubaofs_tpu.blobstore import trace
from chubaofs_tpu.ops import bitmatrix, gf256
from chubaofs_tpu.utils.locks import SanitizedLock

BITS = 8


def unpack_bits(x: jax.Array) -> jax.Array:
    """(..., n, k) uint8 -> (..., 8n, k) int8 of {0,1}, LSB-first rows."""
    bitpos = jnp.arange(BITS, dtype=jnp.uint8)
    b = (x[..., :, None, :] >> bitpos[:, None]) & jnp.uint8(1)
    return b.reshape(*x.shape[:-2], x.shape[-2] * BITS, x.shape[-1]).astype(jnp.int8)


def pack_bits(bits: jax.Array) -> jax.Array:
    """(..., 8m, k) {0,1} -> (..., m, k) uint8."""
    m = bits.shape[-2] // BITS
    b = bits.reshape(*bits.shape[:-2], m, BITS, bits.shape[-1]).astype(jnp.int32)
    weights = jnp.left_shift(jnp.int32(1), jnp.arange(BITS, dtype=jnp.int32))
    return jnp.sum(b * weights[:, None], axis=-2).astype(jnp.uint8)


@jax.jit
def gf_matmul_bytes(mat_bits: jax.Array, shards: jax.Array) -> jax.Array:
    """GF(2^8) matrix product via the bit-matrix lowering (portable XLA path).

    mat_bits: (8r, 8n) int8 GF(2) matrix (from bitmatrix.expand_matrix).
    shards:   (..., n, k) uint8.
    returns:  (..., r, k) uint8 = GFmat @ shards, per batch element.
    """
    bits = unpack_bits(shards)
    acc = jnp.einsum(
        "pi,...ik->...pk",
        mat_bits.astype(jnp.int8),
        bits,
        preferred_element_type=jnp.int32,
    )
    return pack_bits(acc & 1)


FUSED = "pallas-fused"  # ops/pallas_gf.py, compiled by Mosaic (TPU only)
EINSUM = "xla-einsum"  # gf_matmul_bytes above: what an explicit CPU request runs


@functools.cache
def lowering() -> str:
    """The GF matmul lowering this process serves with, decided ONCE from the
    resolved default backend: the compiled fused Pallas kernel on TPU, the
    XLA einsum on anything else (CPU is an explicit request — tests, the
    dryrun mesh). A backend that fails to initialise raises here; it is never
    read as "not a TPU". ops/device.describe() and the codec's per-lowering
    job counter make the answer visible from outside the process."""
    return FUSED if jax.default_backend() == "tpu" else EINSUM


def _use_fused() -> bool:
    return lowering() == FUSED


def gf_matmul_dispatch(mat_bits: jax.Array, shards: jax.Array) -> jax.Array:
    """The process's lowering (see ``lowering``) for a standalone call."""
    if _use_fused():
        from chubaofs_tpu.ops import pallas_gf

        return pallas_gf.gf_matmul_bytes_fused(mat_bits, shards)
    return gf_matmul_bytes(mat_bits, shards)


def group_stack(mat_bits: np.ndarray, batch: int) -> tuple[np.ndarray, int]:
    """(block-diagonal stacked byte-major matrix, g) for a batch of stripes.

    MXU row-filling (PERF.md): one EC(12,4) generator is 32x96 bits on the
    128x128 systolic array; kron(I_g, mat) over g stripes viewed as one wide
    (g*n, k) stripe raises encode from 54 to ~130 GB/s on v5e-1. g divides
    batch and respects the 128-row / 512-col caps (pallas_gf.pick_group);
    g == 1 (and the matrix unchanged) under the einsum lowering or for
    indivisible batches.
    """
    mat_bits = np.asarray(mat_bits, np.int8)
    g = _group_count(mat_bits.shape, batch)
    if g == 1:
        return mat_bits, 1
    return np.kron(np.eye(g, dtype=np.int8), mat_bits), g


def _group_count(bits_shape: tuple[int, int], batch: int) -> int:
    if not _use_fused() or bits_shape[0] == 0:
        return 1
    from chubaofs_tpu.ops import pallas_gf

    return pallas_gf.pick_group(batch, *bits_shape)


class _Held:
    """A bounded map: at most ``bound`` entries, the oldest out. A lookup is
    one dict read and takes no lock; threads that miss the same key at once
    each compute, the first put wins and every one of them gets THAT value."""

    def __init__(self, bound: int):
        self.bound = bound
        self._d: dict = {}
        self._lock = SanitizedLock(name="rs.held")

    def get(self, key):
        return self._d.get(key)

    def put(self, key, value):
        with self._lock:
            value = self._d.setdefault(key, value)
            while len(self._d) > self.bound:
                del self._d[next(iter(self._d))]
        return value

    def __len__(self) -> int:
        return len(self._d)


class MatrixPlan:
    """One GF(2^8) matrix as a launch needs it, each part made once: the
    content key (what the codec service groups a batch by), an immutable copy
    of the matrix, and its GF(2) bits on first need. The device operands made
    from it live in ``_OPERANDS`` by (key, g), so a plan built afresh from an
    equal matrix finds them without expanding anything."""

    __slots__ = ("key", "mat", "shape", "_bits")

    def __init__(self, mat: np.ndarray | None = None, bits: np.ndarray | None = None):
        """From the matrix (``bits`` too where the caller has them expanded), or
        from byte-major GF(2) bits alone (gf_matmul_hostbatch's numpy callers)."""
        if mat is None:
            bits = np.ascontiguousarray(bits, np.int8)
            self.key, self.mat, self.shape = ("bits", bits.shape, bits.tobytes()), None, bits.shape
        else:
            mat = np.ascontiguousarray(mat, np.uint8)
            raw = mat.tobytes()
            self.key = ("gf", mat.shape, raw)
            self.mat = np.frombuffer(raw, np.uint8).reshape(mat.shape)  # read-only
            self.shape = (mat.shape[0] * BITS, mat.shape[1] * BITS)  # of the bits
        self._bits = bits

    def bits(self) -> np.ndarray:
        """(8r, 8c) int8, byte-major (bitmatrix.expand_matrix's order): expanded
        on the first call."""
        if self._bits is None:
            self._bits = bitmatrix.expand_matrix(self.mat).astype(np.int8)
        return self._bits

    @property
    def expanded(self) -> bool:
        return self._bits is not None

    def ready(self, batch: int) -> bool:
        """Is the operand a batch of this many stripes launches with resident?"""
        return _OPERANDS.get((self.key, _group_count(self.shape, batch))) is not None

    def operand(self, batch: int) -> tuple[jax.Array, int]:
        """(the resident operand, g) for a batch of this many stripes: the
        bits, kron(I_g, .) where the lowering group-stacks, the kernel's
        plane-major order and the placement on the device, done on the first
        batch that needs them."""
        g = _group_count(self.shape, batch)
        op = _OPERANDS.get((self.key, g))
        if op is None:
            form, _ = group_stack(self.bits(), batch)
            if _use_fused():
                from chubaofs_tpu.ops import pallas_gf

                form = pallas_gf.plane_major(form)
            op = _OPERANDS.put((self.key, g),
                               jax.device_put(np.ascontiguousarray(form, np.int8)))
        return op, g


# (plan key, g) -> the matrix resident on the device in the lowering's layout:
# at most 128 x 512 int8 an entry where g > 1 (pick_group's caps)
_OPERANDS = _Held(512)


def gf_matmul_hostbatch(mat_bits: np.ndarray | MatrixPlan, shards: np.ndarray) -> np.ndarray:
    """Host-boundary batched GF matmul with MXU group-stacking.

    shards: host (..., n, k) uint8 -> host (..., r, k). The group view
    (b, n, k) -> (b/g, g*n, k) is a free numpy reshape HERE; on device the
    same reshape physically rearranges the sublane-tiled HBM buffer (measured
    131 -> 53 GB/s), which is why stacking lives at the host boundary — where
    this storage system's stripes originate anyway (network buffers, chunk
    files). This is the batch entry the codec service and repair planes use.
    mat_bits is byte-major GF(2) bits, or the MatrixPlan a caller already
    holds (the codec service: nothing is serialised to find the operand).
    Three stages split its wall time: hostbatch.group (the resident operand
    looked up; made on the first batch of a matrix at a group count),
    hostbatch.launch (H2D of the shards + enqueue; returns a device array)
    and hostbatch.fetch (the wait for the kernel + D2H).
    """
    shards = np.asarray(shards, np.uint8)
    plan = mat_bits if isinstance(mat_bits, MatrixPlan) else MatrixPlan(bits=mat_bits)
    lead, n, k = shards.shape[:-2], shards.shape[-2], shards.shape[-1]
    r = plan.shape[0] // BITS
    b = 1
    for d in lead:
        b *= d
    if b == 0 or r == 0 or k == 0:
        return np.zeros((*lead, r, k), np.uint8)
    with trace.stage("hostbatch.group"):
        operand, g = plan.operand(b)
    with trace.stage("hostbatch.launch"):
        grouped = shards.reshape(b // g, g * n, k)
        if _use_fused():
            from chubaofs_tpu.ops import pallas_gf

            out = pallas_gf.gf_matmul_planes(operand, grouped)
        else:
            out = gf_matmul_bytes(operand, grouped)
    with trace.stage("hostbatch.fetch"):
        return np.asarray(out).reshape(*lead, r, k)


@jax.jit
def xor_reduce(shards: jax.Array) -> jax.Array:
    """XOR over the shard axis: (..., n, k) -> (..., k). Used by CRC/verify paths."""
    return jax.lax.reduce(
        shards, np.uint8(0), jax.lax.bitwise_xor, dimensions=(shards.ndim - 2,)
    )


class RSKernel:
    """Compiled GF(2^8) codec for one (n, m) systematic layout.

    Host-side numpy builds the generator and per-repair decode matrices; the device
    only ever sees one shape-polymorphic bit-matmul. All methods accept numpy or
    jax arrays with shape (n_in, k) or (B, n_in, k).
    """

    def __init__(self, n: int, m: int):
        if n <= 0 or m < 0 or n + m > 256:
            raise ValueError(f"invalid RS layout n={n} m={m}")
        self.n = n
        self.m = m
        self.total = n + m
        self.gen = gf256.systematic_generator(n, m)  # (n+m, n) uint8
        # numpy, NOT jnp: committing to the default device here would break
        # sharded/CPU call sites (the multi-chip dryrun must never touch the
        # default backend); inside jit a numpy constant is embedded and placed
        # by XLA wherever the computation runs.
        self.parity_bits = bitmatrix.expand_matrix(self.gen[n:, :]).astype(np.int8)
        self.parity_plan = MatrixPlan(self.gen[n:, :], self.parity_bits)
        # damage pattern -> its matrix: a pure function of the pattern for
        # this (n, m), so the GF inverse runs once a pattern, not once a blob
        self._window = _Held(256)
        self._repair = _Held(256)

    # -- encode ------------------------------------------------------------
    #
    # portable=True forces the XLA einsum lowering, which GSPMD partitions
    # cleanly over sharded operands; the fused Pallas kernel has no automatic
    # partitioning rule, so sharded call sites (parallel/mesh.py) must opt out
    # of the dispatch.

    def encode_parity(self, data: jax.Array, *, portable: bool = False) -> jax.Array:
        """(..., n, k) data -> (..., m, k) parity."""
        # hot-path failpoint: the guard test in tests/test_chaos.py pins this
        # to zero measurable overhead while unarmed
        chaos.failpoint("rs.encode")
        fn = gf_matmul_bytes if portable else gf_matmul_dispatch
        return fn(self.parity_bits, jnp.asarray(data))

    def encode(self, data: jax.Array, *, portable: bool = False) -> jax.Array:
        """(..., n, k) data -> (..., n+m, k) full stripe."""
        data = jnp.asarray(data)
        return jnp.concatenate(
            [data, self.encode_parity(data, portable=portable)], axis=-2
        )

    # -- reconstruct -------------------------------------------------------

    def repair_matrix(self, bad_idx: list[int], data_only: bool = False) -> tuple[np.ndarray, list[int], list[int]]:
        """Host-side: (matrix mapping survivors->missing, survivor rows, missing rows).

        survivor rows are the first n present indices; matrix is GF(2^8) of shape
        (len(missing), n), already verified invertible via decode_matrix. The
        matrix is the held one, read-only: pad or edit a copy.
        """
        (plan, present, missing), _ = self.held_repair(bad_idx, data_only)
        return plan.mat, list(present), list(missing)

    def held_repair(self, bad_idx: list[int], data_only: bool = False):
        """((MatrixPlan, survivor rows, missing rows), was it held) for
        repair_matrix's arguments: computed once a pattern."""
        key = (tuple(sorted(set(int(i) for i in bad_idx))), bool(data_only))
        held = self._repair.get(key)
        if held is not None:
            return held, True
        bad = list(key[0])
        for i in bad:
            if not 0 <= i < self.total:
                raise ValueError(f"bad shard index {i}")
        if len(bad) > self.m:
            raise ValueError(f"{len(bad)} missing shards > m={self.m}, unrecoverable")
        present = [i for i in range(self.total) if i not in set(bad)][: self.n]
        dec = gf256.decode_matrix(self.gen, present)  # (n, n)
        missing = [i for i in bad if i < self.n] if data_only else bad
        mat = gf256.gf_matmul(self.gen[np.asarray(missing), :], dec) if missing else np.zeros((0, self.n), np.uint8)
        return self._repair.put(key, (MatrixPlan(mat), tuple(present), tuple(missing))), False

    def window_matrix(self, present: list[int], want: list[int]) -> np.ndarray:
        """Row-sliced decode matrix for ranged reads: the GF(2^8) map from
        exactly n survivor rows (in `present` order) to exactly the `want`
        shard rows — gen[want] @ inv(gen[present]).

        Unlike repair_matrix this takes the caller's survivor CHOICE as-is
        (the access layer's windowed gather already picked which shards to
        fetch) and computes only the rows the byte window needs, so degraded
        decode cost scales with the window, not the stripe. RS is column-
        independent, so the same matrix applied to column-sliced survivors
        yields the identical column slice of the wanted shards. The matrix
        is the held one, read-only: pad or edit a copy.
        """
        return self.held_window(present, want)[0].mat

    def held_window(self, present: list[int], want: list[int]) -> tuple[MatrixPlan, bool]:
        """(window_matrix's matrix as a MatrixPlan, was it held): the inverse
        and the product run once a (present, want) pattern."""
        key = (tuple(int(i) for i in present), tuple(int(i) for i in want))
        plan = self._window.get(key)
        if plan is not None:
            return plan, True
        present, want = key
        if len(present) != self.n:
            raise ValueError(
                f"window decode needs exactly n={self.n} survivors, "
                f"got {len(present)}")
        for i in present + want:
            if not 0 <= i < self.total:
                raise ValueError(f"bad shard index {i}")
        if not want:
            mat = np.zeros((0, self.n), np.uint8)
        else:
            dec = gf256.decode_matrix(self.gen, list(present))  # (n, n)
            mat = gf256.gf_matmul(self.gen[np.asarray(want), :], dec)
        return self._window.put(key, MatrixPlan(mat)), False

    def repair_plan(self, bad_idx: list[int], data_only: bool = False):
        """Device-ready repair plan: (repair_bits, present, missing) numpy arrays.

        Shared by reconstruct, the sharded codec step, and the benches so the
        bit-matrix repair lowering lives in exactly one place. Kept as numpy so
        closing over a plan inside jit never commits to the default device.
        """
        (plan, present, missing), _ = self.held_repair(bad_idx, data_only)
        return plan.bits(), np.asarray(present, np.int32), np.asarray(missing, np.int32)

    @staticmethod
    def _device_plan(mat, present, missing):
        mat_bits = bitmatrix.expand_matrix(mat).astype(np.int8)
        return mat_bits, np.asarray(present, np.int32), np.asarray(missing, np.int32)

    def repair_plan_padded(self, bad_idx: list[int], data_only: bool = False):
        """Fixed-shape repair plan: always m repair rows, so ONE compiled step
        serves every missing pattern as runtime data — changing the set of
        missing shards never recompiles (the static-shape discipline the
        sharded codec step needs). Padded slots carry the GF identity row of
        survivor 0 and target survivor 0's own position: a value-level no-op
        write. Returns (repair_bits (8m, 8n) int8, present (n,), missing (m,)).
        """
        mat, present, missing = self.repair_matrix(bad_idx, data_only)
        pad = self.m - len(missing)
        if pad:
            id_rows = np.zeros((pad, self.n), np.uint8)
            id_rows[:, 0] = 1  # GF row e_0: recomputes survivor 0 exactly
            mat = np.concatenate([mat, id_rows], axis=0) if len(missing) else id_rows
            missing = list(missing) + [present[0]] * pad
        return self._device_plan(mat, present, missing)

    def apply_repair(self, plan, shards: jax.Array, *, portable: bool = False) -> jax.Array:
        """Apply a repair_plan to (..., n+m, k) shards (jit-friendly)."""
        mat_bits, present, missing = plan
        if missing.shape[0] == 0:
            return shards
        survivors = jnp.take(shards, present, axis=-2)
        fn = gf_matmul_bytes if portable else gf_matmul_dispatch
        rows = fn(mat_bits, survivors)
        return shards.at[..., missing, :].set(rows)

    def reconstruct(self, shards, bad_idx: list[int], data_only: bool = False):
        """shards (..., n+m, k) with garbage at bad_idx -> repaired (..., n+m, k)."""
        shards = jnp.asarray(shards)
        _, _, missing = self.repair_matrix(bad_idx, data_only)
        if not missing:
            return shards
        return self.apply_repair(self.repair_plan(bad_idx, data_only), shards)

    # -- verify ------------------------------------------------------------

    def verify(self, shards, *, portable: bool = False) -> jax.Array:
        """(..., n+m, k) -> scalar/batch bool: parity rows match re-encoded parity."""
        shards = jnp.asarray(shards)
        expect = self.encode_parity(shards[..., : self.n, :], portable=portable)
        got = shards[..., self.n :, :]
        return jnp.all(expect == got, axis=(-2, -1))


@functools.lru_cache(maxsize=64)
def get_kernel(n: int, m: int) -> RSKernel:
    """Process-wide kernel cache (generator construction is setup-time work)."""
    return RSKernel(n, m)
