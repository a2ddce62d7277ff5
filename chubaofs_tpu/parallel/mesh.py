"""Mesh + sharding layer for the codec: the framework's DP/SP scale-out axes.

The reference scales erasure coding by fanning stripes out to goroutines on many
hosts (access stream_put.go:193-442; scheduler bulk repair). The TPU-native
equivalent is a jax.sharding.Mesh with two axes:

  * ``dp`` (data/stripe parallel) — independent stripes across devices; the analog
    of the reference's per-blob goroutines.
  * ``sp`` (shard-length / "sequence" parallel) — the byte axis *within* a stripe
    split across devices, so a single huge stripe (the long-context analog, SURVEY
    §5 "stripe batch size × shard count") exceeds one chip's HBM/compute. GF
    encoding is columnwise-independent, so sp sharding needs no collectives for
    encode; only verify's final reduction crosses devices (an AND via jnp.all,
    lowered to an XLA all-reduce over ICI).

The bit-generator matrices are tiny (<= 320x320 int8) and replicated.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from chubaofs_tpu.ops import rs


def codec_mesh(devices=None, dp: int | None = None, sp: int | None = None) -> Mesh:
    """Build a (dp, sp) mesh over the given devices (default: all)."""
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if dp is None and sp is None:
        sp = 2 if n % 2 == 0 and n > 1 else 1
        dp = n // sp
    elif dp is None:
        dp = n // sp
    elif sp is None:
        sp = n // dp
    if dp * sp != n:
        raise ValueError(f"dp*sp = {dp}*{sp} != {n} devices")
    arr = np.asarray(devices).reshape(dp, sp)
    return Mesh(arr, axis_names=("dp", "sp"))


def shard_stripes(mesh: Mesh, stripes) -> jax.Array:
    """Place (B, n, k) stripes: B over dp, k over sp, shard axis replicated.

    Host data goes straight to the mesh's devices — no intermediate commit to
    the default backend (which may be a different platform than the mesh).
    """
    if not isinstance(stripes, jax.Array):
        stripes = np.asarray(stripes)
    return jax.device_put(stripes, NamedSharding(mesh, P("dp", None, "sp")))


def group_view(data: np.ndarray, g: int) -> np.ndarray:
    """Host-boundary group view: (B, n, k) -> (B/g, g*n, k). A free numpy
    reshape here; on device the same reshape physically rearranges the
    sublane-tiled buffer (PERF.md "group stacking")."""
    b, n, k = data.shape
    assert b % g == 0, (b, g)
    return data.reshape(b // g, g * n, k)


def ungroup_stripe(stripe: np.ndarray, g: int, n: int, m: int,
                   b: int | None = None) -> np.ndarray:
    """Host-boundary inverse for encoded stripes: grouped (B/g, g*n + g*m, k)
    -> per-stripe (B, n+m, k). The grouped layout keeps the g stripes' data
    rows first and their parity rows after (block order), so the split is two
    views plus one concatenate. Pass ``b`` (the original stripe count) to
    drop the zero-padding stripes an uneven batch leaves inside the final
    group — the device can't slice a partial group, so it happens here."""
    stripe = np.asarray(stripe)
    bg, rows, k = stripe.shape
    assert rows == g * (n + m), (stripe.shape, g, n, m)
    data = stripe[:, : g * n, :].reshape(bg * g, n, k)
    par = stripe[:, g * n :, :].reshape(bg * g, m, k)
    out = np.concatenate([data, par], axis=1)
    return out[:b] if b is not None else out


def _grouped_row(s: int, gi: int, g: int, n: int, m: int) -> int:
    """Stripe-local shard index s (0..n+m) of slab gi -> grouped stripe row."""
    return gi * n + s if s < n else g * n + gi * m + (s - n)


def _select_gf(mesh: Mesh, fused: bool | None, interpret: bool):
    """(gf, use_fused) for this mesh. Auto-select keys off the MESH's
    platform, which need not be the default backend's (a CPU test mesh in a
    process whose default is the TPU): Mosaic cannot compile for CPU devices.
    interpret=True forces the Pallas kernel in interpret mode (CPU-mesh
    tests of the real kernel)."""
    mesh_platform = next(iter(mesh.devices.flat)).platform
    use_fused = interpret or (
        fused if fused is not None else mesh_platform == "tpu"
    )

    def gf(mat_bits, x):
        if use_fused:
            from chubaofs_tpu.ops import pallas_gf

            # numpy matrices pass through unconverted so the plane-major
            # permutation runs in numpy at trace time; traced matrices pay
            # a tiny in-graph gather instead
            return pallas_gf.gf_matmul_bytes_fused(mat_bits, x,
                                                   interpret=interpret)
        return rs.gf_matmul_bytes(mat_bits, x)

    return gf, use_fused


def sharded_gf_matmul(mesh: Mesh, *, fused: bool | None = None,
                      interpret: bool = False):
    """Mesh-wide drop-in for ``rs.gf_matmul_hostbatch``: host (B, n, k)
    batches x a byte-major bit matrix -> host (B, r, k), sharded B over
    ``dp`` and k over ``sp``, with the MXU group-stacked layout taken at the
    host boundary (PERF.md). This is how CodecService — and therefore the
    whole blobstore data plane above it (access PUT/GET, scheduler bulk
    repair) — runs on more than one chip: the service stays a single queue,
    but every drained batch fans out across the mesh.

    The matrix rides as a RUNTIME argument (replicated), so every repair
    pattern of the same shape shares one compiled program — exactly the
    ``sharded_codec_step`` plan contract, applied to the service's generic
    matmul jobs."""
    gf, use_fused = _select_gf(mesh, fused, interpret)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(), P("dp", None, "sp")),
        out_specs=P("dp", None, "sp"),
        check_vma=False,
    )
    def mm(mat, data):
        return gf(mat, data)

    jitted = jax.jit(mm)
    replicated = NamedSharding(mesh, P())
    dp, sp = mesh.shape["dp"], mesh.shape["sp"]

    def run(mat_bits: np.ndarray, batch: np.ndarray) -> np.ndarray:
        batch = np.asarray(batch, np.uint8)
        mat_bits = np.asarray(mat_bits, np.int8)
        b, n, k = batch.shape
        r = mat_bits.shape[0] // 8
        if b == 0 or r == 0 or k == 0:
            return np.zeros((b, r, k), np.uint8)
        if use_fused:
            from chubaofs_tpu.ops import pallas_gf

            # cap g so grouping never collapses the batch below dp (every
            # mesh row must keep real stripes, not padding)
            g = pallas_gf.pick_group(b, *mat_bits.shape, cap=max(1, b // dp))
        else:
            g = 1
        mat_s = np.kron(np.eye(g, dtype=np.int8), mat_bits) if g > 1 else mat_bits
        data = group_view(batch, g) if g > 1 else batch
        pad_rows = (-data.shape[0]) % dp
        if pad_rows:  # zero stripes encode trivially; sliced back out below
            data = np.concatenate(
                [data, np.zeros((pad_rows, g * n, k), np.uint8)])
        kpad = (-k) % (sp * 128)
        if kpad:
            data = np.pad(data, ((0, 0), (0, 0), (0, kpad)))
        with mesh:
            out = jitted(jax.device_put(mat_s, replicated),
                         shard_stripes(mesh, data))
        out = np.asarray(out)[: b // g, :, :k]
        return out.reshape(b, r, k)

    # the CodecService's per-lowering job counter reads this
    run.lowering = ("pallas-interpret" if interpret
                    else rs.FUSED if use_fused else rs.EINSUM)
    run.jitted = jitted  # AOT compile checks lower this without devices
    return run


def sharded_codec_step(
    mesh: Mesh, n: int, m: int, *, fused: bool | None = None,
    interpret: bool = False, group: int = 1
):
    """Jitted full codec step over the mesh: encode -> verify -> repair.

    This is the flagship distributed 'step' (the training-step analog): one batch
    of stripes goes through the complete PUT+scrub+repair pipeline. Returns
    ``run(data, bad_idx=(0, n))`` mapping (B, n, k) uint8 data stripes to
    (stripe, ok (B,), repaired).

    Sharding story: the step is a ``jax.shard_map`` over (dp, sp) — each device
    runs the FUSED Pallas kernel on its local block (GF math is
    columnwise-independent, so no collectives except verify's AND over sp,
    a psum on ICI). ``fused=None`` auto-selects: Pallas on TPU backends, the
    XLA einsum lowering elsewhere; ``interpret=True`` forces the Pallas kernel
    in interpret mode (CPU-mesh tests of the real kernel).

    ``group=g`` runs the MXU group-stacked layout per device (PERF.md: the
    single-chip 54 -> 122 GB/s step, carried to the sharded path): g stripes
    are viewed as one wide (g*n, k) stripe AT THE HOST BOUNDARY (free numpy
    reshape in ``run``) and all matrices — generator and runtime repair plans
    alike — are kron-stacked to fill the MXU rows. With group > 1:
      * pass HOST (numpy) batches — a device-resident input is staged through
        the host (D2H + re-upload), because only the host view is free;
      * the stripe and repaired outputs stay in the grouped device layout —
        convert with ``ungroup_stripe(out, g, n, m, b=B)``, which also drops
        the zero-pad stripes an uneven batch leaves inside the final group
        (the device cannot slice a partial group);
      * ``ok`` is always per-stripe and sliced to B.

    The repair pattern is RUNTIME data via ``repair_plan_padded`` — changing
    ``bad_idx`` between calls never recompiles (the kron stacking preserves
    static shapes). Batches that don't divide dp*group are zero-padded in and
    sliced out (zero stripes encode/verify trivially).
    """
    g = int(group)
    assert g >= 1
    kernel = rs.get_kernel(n, m)
    gn, gm = g * n, g * m
    if g == 1:
        parity_bits = kernel.parity_bits
    else:
        parity_bits = np.kron(np.eye(g, dtype=np.int8), kernel.parity_bits)
    gf, use_fused = _select_gf(mesh, fused, interpret)
    sp_size = mesh.shape["sp"]
    trace_count = [0]

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P("dp", None, "sp"), P(), P(), P()),
        out_specs=(P("dp", None, "sp"), P("dp"), P("dp", None, "sp")),
        # pallas_call carries no varying-mesh-axes metadata; the out_specs
        # above are the replication contract, checked by the tests numerically
        check_vma=False,
    )
    def step(data, repair_bits, present, missing):
        trace_count[0] += 1  # trace-time only: counts compilations, not calls
        parity = gf(parity_bits, data)  # (B/(dp*g), g*m, k/sp) per device
        stripe = jnp.concatenate([data, parity], axis=-2)
        # verify: recompute parity from the stripe's data rows, AND over sp;
        # row-wise first so ok stays PER STRIPE even in the grouped layout
        expect = gf(parity_bits, stripe[..., :gn, :])
        eq_rows = jnp.all(expect == stripe[..., gn:, :], axis=-1)  # (b, g*m)
        ok_local = jnp.all(eq_rows.reshape(*eq_rows.shape[:-1], g, m), axis=-1)
        ok = jax.lax.psum(ok_local.astype(jnp.int32), "sp") == sp_size
        ok = ok.reshape(-1)  # (b*g,): per original stripe
        # repair: survivors -> missing rows via the runtime plan
        survivors = jnp.take(stripe, present, axis=-2)
        rows = gf(repair_bits, survivors)
        repaired = stripe.at[..., missing, :].set(rows)
        return stripe, ok, repaired

    jitted = jax.jit(step)
    replicated = NamedSharding(mesh, P())

    @functools.lru_cache(maxsize=64)
    def plan_for(bad: tuple) -> tuple:
        # once per pattern: the O(n^3) host-side inversion AND the replicated
        # broadcast to every mesh device (repeat steps transfer nothing).
        # With group > 1 the plan is kron-stacked and its survivor/missing
        # coordinates expanded to grouped stripe rows — shapes stay static,
        # so changing patterns still never recompiles.
        mat, present, missing = kernel.repair_plan_padded(list(bad))
        if g > 1:
            mat = np.kron(np.eye(g, dtype=np.int8), mat)
            present = np.asarray(
                [_grouped_row(int(s), gi, g, n, m)
                 for gi in range(g) for s in present], np.int32)
            missing = np.asarray(
                [_grouped_row(int(s), gi, g, n, m)
                 for gi in range(g) for s in missing], np.int32)
        plan = (mat, present, missing)
        return tuple(jax.device_put(a, replicated) for a in plan)

    def run(data, bad_idx=(0, n)):
        args = plan_for(tuple(sorted(set(int(i) for i in bad_idx))))
        if isinstance(data, jax.Array) and g > 1:
            # the group view is only free at the host boundary: device inputs
            # pay a D2H + re-upload here (see docstring — pass numpy batches)
            data = np.asarray(data)
        if not isinstance(data, jax.Array):
            data = np.asarray(data)
        b = data.shape[0]
        pad = (-b) % (mesh.shape["dp"] * g)
        if pad:
            # pad in the input's own space: device arrays stay on device
            xp = jnp if isinstance(data, jax.Array) else np
            data = xp.concatenate(
                [data, xp.zeros((pad, *data.shape[1:]), xp.uint8)], axis=0
            )
        if g > 1:
            data = group_view(data, g)
        data = shard_stripes(mesh, data)
        with mesh:
            stripe, ok, repaired = jitted(data, *args)
        if pad:
            nb = b // g + (1 if b % g else 0) if g > 1 else b
            stripe = stripe[:nb]
            repaired = repaired[:nb]
            ok = ok[:b]
        return stripe, ok, repaired

    run.trace_count = trace_count
    run.group = g
    return run
