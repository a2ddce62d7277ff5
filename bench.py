"""Benchmark: all five BASELINE.json EC configs on one TPU chip.

Headline metric (north star): EC(12,4) 8 MiB-stripe encode, target >= 40 GB/s
per chip on v5e-1 (vs_baseline = value/40). The other four configs from
BASELINE.json ride along in the same JSON line:

  * EC(4,2)  1 MiB stripe  — unit-bench config
  * EC(6,3)  4 MiB stripe  — access PUT-path streaming encode
  * EC(12,4) 8 MiB stripe  — encode + single-missing reconstruct
  * EC(12,4) 8 MiB stripe, 3 missing, bulk repair — stripes/sec (the
    scheduler's 10k-stripe migrate workload, measured as sustained device
    rate on resident batches; see PERF.md for the traffic accounting)
  * EC(20,4)+L2 16 MiB stripe — LRC archive config: global + per-AZ local
    parity encode in one jitted step

Prints exactly ONE JSON line on stdout; diagnostics go to stderr. Runs on the
accelerator or not at all: a device missing from DEVICE_PEAKS, or a kernel the
compiler refuses, is an error and a non-zero exit, never a number.

Methodology: inputs resident in HBM; SLOPE timing — run N1 then N2 pipelined
iterations each ended by jax.block_until_ready, and divide the time DELTA by
the iteration delta. Constant costs (enqueue, sync overhead) cancel in the
subtraction, leaving per-call device time. Reconstruct is measured the way
blobnode repair runs it (SURVEY §3.5): survivors in, repaired rows out.
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from chubaofs_tpu.ops import rs

TARGET_GBPS = 40.0
HEADLINE_METRIC = "ec12p4_encode_8mib_stripe"


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def throughput(fn, args, n1=10, n2=40, runs=3, passes=3,
               floor: float = 0.0) -> float:
    """Seconds per call via slope timing (see module docstring).

    Median across ``passes`` passes, each itself a median-of-``runs`` slope —
    robust to host-side stalls (the one-chip machine shares its host's cores)
    without the low-tail bias a min-of-samples would introduce (an extreme
    statistic would crown exactly the deflated slopes the medians reject).
    ``floor`` is the physical lower bound on seconds-per-call (HBM peak):
    sub-floor passes are corrupted measurements (both legs raced the same
    stall) and are discarded; if NOTHING plausible remains the run errors out
    with the raw slopes rather than printing impossible numbers."""

    def timed(iters: int) -> float:
        t0 = time.perf_counter()
        out = None
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        return time.perf_counter() - t0

    timed(2)  # compile + warm
    plausible: list[float] = []
    raw: list[float] = []
    for _ in range(passes):
        # median of the deltas: a single stall in either leg must not deflate
        # the subtraction (min-of-deltas would lock in a corrupted run)
        deltas = sorted(timed(n2) - timed(n1) for _ in range(runs))
        per_iter = deltas[len(deltas) // 2] / (n2 - n1)
        raw.append(per_iter)
        if per_iter >= max(floor, 0.0) and per_iter > 0:
            plausible.append(per_iter)
    if not plausible:
        raise RuntimeError(f"unstable timing: no plausible pass; slopes={raw}")
    plausible.sort()
    return plausible[len(plausible) // 2]


# Published per-chip peaks, keyed by jax's device_kind. One table, with its
# source; a device that is not in it is an error, not a default.
DEVICE_PEAKS = {
    # Google Cloud documentation, "TPU v5e": 819 GB/s HBM, 393 TOP/s int8
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "int8_ops_per_s": 393e12},
}


def hbm_peak(dev) -> float:
    """HBM peak bytes/sec of the device the bench runs on."""
    try:
        return DEVICE_PEAKS[dev.device_kind]["hbm_bytes_per_s"]
    except KeyError:
        raise RuntimeError(
            f"device_kind {dev.device_kind!r} is not in bench.DEVICE_PEAKS "
            f"({sorted(DEVICE_PEAKS)}): add its published peaks, with their "
            "source, before benchmarking on it") from None


def hbm_floor(total_bytes_moved: int, dev) -> float:
    """Physical seconds floor: moving the op's bytes at the device's HBM peak."""
    return total_bytes_moved / hbm_peak(dev)


def stage_grouped(dev, host, mat_bits):
    """Device-resident batch in the codec's canonical GROUP-STACKED layout.

    host: (B, n, k) uint8. The (B, n, k) -> (B/g, g*n, k) view is a free numpy
    reshape at the host boundary (rs.gf_matmul_hostbatch does the same on the
    live path); the stacked generator fills the MXU rows (rs.group_stack,
    PERF.md). Returns (stacked numpy matrix, staged device data).
    """
    b, n, k = host.shape
    mat_s, g = rs.group_stack(mat_bits, b)
    return mat_s, jax.device_put(jnp.asarray(host.reshape(b // g, g * n, k)), dev)


def bench_encode(rng, dev, n, m, stripe_bytes, batch) -> float:
    """Encode GB/s (payload basis) for one (n, m, stripe) config."""
    k = -(-stripe_bytes // n // 128) * 128  # 128-aligned shard length
    kernel = rs.get_kernel(n, m)
    host = rng.integers(0, 256, (batch, n, k), dtype=np.uint8)
    mat_s, data = stage_grouped(dev, host, kernel.parity_bits)
    # the numpy matrix closed over bakes in as a compile-time constant
    per = throughput(jax.jit(lambda s: rs.gf_matmul_dispatch(mat_s, s)), (data,),
                     floor=hbm_floor(batch * (n + m) * k, dev))
    return batch * n * k / per / 1e9


def bench_reconstruct(rng, dev, n, m, stripe_bytes, batch, missing) -> tuple[float, float]:
    """(GB/s payload basis, stripes/sec) repairing `missing` shards per stripe,
    the blobnode-repair way: survivors in, missing rows out."""
    k = -(-stripe_bytes // n // 128) * 128
    kernel = rs.get_kernel(n, m)
    mat_bits, present, _ = kernel.repair_plan(list(missing))
    data = rng.integers(0, 256, (batch, n, k), dtype=np.uint8)
    stripe = np.asarray(jax.jit(kernel.encode)(jax.device_put(jnp.asarray(data), dev)))
    mat_s, survivors = stage_grouped(dev, stripe[:, present, :], mat_bits)
    per = throughput(jax.jit(lambda s: rs.gf_matmul_dispatch(mat_s, s)), (survivors,),
                     floor=hbm_floor(batch * (n + len(missing)) * k, dev))
    return batch * n * k / per / 1e9, batch / per


def bench_lrc_encode(rng, dev, batch) -> float:
    """EC(20,4)+L2 archive config: ALL parity (4 global + 2 per-AZ local) in
    one composed-generator matmul (encoder.lrc_parity_matrix) — the TPU-first
    replacement for the reference's two-stage global+local encode. Geometry
    comes from the model zoo's ARCHIVE entry (shared with the dryrun)."""
    from chubaofs_tpu.codec.encoder import lrc_parity_matrix
    from chubaofs_tpu.models import ARCHIVE
    from chubaofs_tpu.ops import bitmatrix

    t = ARCHIVE.tactic
    k = ARCHIVE.shard_len
    mat_bits = bitmatrix.expand_matrix(lrc_parity_matrix(t)).astype(np.int8)
    host = rng.integers(0, 256, (batch, t.N, k), dtype=np.uint8)
    mat_s, data = stage_grouped(dev, host, mat_bits)
    per = throughput(jax.jit(lambda s: rs.gf_matmul_dispatch(mat_s, s)), (data,),
                     floor=hbm_floor(batch * (t.N + t.M + t.L) * k, dev))
    return batch * t.N * k / per / 1e9


def main() -> None:
    from chubaofs_tpu.ops import device

    cache_dir = device.enable_compile_cache()
    dev = jax.devices()[0]  # in-process, once: this process owns the chip
    hbm_peak(dev)  # an unknown device fails here, before any timing
    log(f"device={dev} {device.describe()} compile_cache={cache_dir}")
    rng = np.random.default_rng(0)
    MiB = 1 << 20

    cfg: dict[str, float] = {}

    cfg["ec4p2_encode_1mib_gbps"] = round(
        bench_encode(rng, dev, 4, 2, 1 * MiB, batch=64), 3
    )
    log(f"EC(4,2) 1MiB encode: {cfg['ec4p2_encode_1mib_gbps']} GB/s")

    cfg["ec6p3_encode_4mib_gbps"] = round(
        bench_encode(rng, dev, 6, 3, 4 * MiB, batch=24), 3
    )
    log(f"EC(6,3) 4MiB encode: {cfg['ec6p3_encode_4mib_gbps']} GB/s")

    headline = bench_encode(rng, dev, 12, 4, 8 * MiB, batch=16)
    cfg["ec12p4_encode_8mib_gbps"] = round(headline, 3)
    log(f"EC(12,4) 8MiB encode: {headline:.2f} GB/s")

    rec_gbps, _ = bench_reconstruct(rng, dev, 12, 4, 8 * MiB, batch=16, missing=[0])
    cfg["ec12p4_reconstruct_1miss_gbps"] = round(rec_gbps, 3)
    log(f"EC(12,4) reconstruct(1 missing): {rec_gbps:.2f} GB/s")

    bulk_gbps, stripes_sec = bench_reconstruct(
        rng, dev, 12, 4, 8 * MiB, batch=64, missing=[0, 5, 12]
    )
    cfg["ec12p4_bulk_repair_3miss_stripes_per_sec"] = round(stripes_sec, 1)
    cfg["ec12p4_bulk_repair_3miss_gbps"] = round(bulk_gbps, 3)
    log(
        f"EC(12,4) bulk repair (3 missing, 64-stripe device batches): "
        f"{stripes_sec:.0f} stripes/s ({bulk_gbps:.2f} GB/s)"
    )

    cfg["ec20p4l2_encode_16mib_gbps"] = round(
        bench_lrc_encode(rng, dev, batch=8), 3
    )
    log(f"EC(20,4)+L2 16MiB encode: {cfg['ec20p4l2_encode_16mib_gbps']} GB/s")

    # /metrics snapshot next to the BENCH_*.json line: the bench figures as
    # gauges plus whatever role registries (codec, raft, ...) this process
    # exercised — perf rounds carry counters alongside throughput lines
    try:
        from chubaofs_tpu.utils import exporter

        breg = exporter.registry("bench")
        for k, v in cfg.items():
            if isinstance(v, (int, float)):
                breg.gauge(k).set(v)
        dump_path = os.environ.get("CFS_METRICS_DUMP", "BENCH_metrics.prom")
        exporter.dump(dump_path)
        log(f"metrics snapshot -> {dump_path}")
    except Exception as e:  # a dump failure must never kill the bench line
        log(f"metrics snapshot failed: {type(e).__name__}: {e}")

    print(
        json.dumps(
            {
                "metric": HEADLINE_METRIC,
                "value": cfg["ec12p4_encode_8mib_gbps"],
                "unit": "GB/s",
                "vs_baseline": round(headline / TARGET_GBPS, 4),
                "configs": cfg,
                "device": device.describe(),
                "compile": device.compile_stats(),
            }
        )
    )


if __name__ == "__main__":
    main()
