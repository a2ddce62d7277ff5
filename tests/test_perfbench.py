"""Hot-path perf harness: runs end-to-end at tiny sizes + loose regression
floors so a pathological slowdown (per-op reconnect, raft tick-gated
proposes, accidental O(n^2) paths) fails the suite rather than silently
rotting the PERF.md numbers. Floors are ~10x under the measured dev-host
figures (PERF.md round-5 section) to stay robust on loaded CI hosts."""

import json
import os
import signal
import subprocess
import sys

import pytest


def test_raft_commit_microbench_floor(tmp_path):
    """Tier-1 batching gate: the in-proc raft-commit microbench (no
    subprocess cluster — seconds, not minutes) with 10x-slack floors, so a
    group-commit regression fails fast. Floors are against tiny-size rates
    (measured ~216 1p / ~1530 8x8 on the 2-vCPU dev host)."""
    from chubaofs_tpu.tools.perfbench import bench_raft_commit

    out = bench_raft_commit(str(tmp_path), n_ops=120)
    assert out["raft_commit_ops_1p"] > 20, out
    assert out["raft_commit_ops_8x8"] > 120, out
    # group commit must actually form multi-entry drained batches
    assert out["raft_commit_batch_8p"] > 1.0, out


def test_put_pipeline_bench_smoke_floor(tmp_path):
    """Tier-1 pipeline gate (ISSUE 4 satellite): the data-path A/B bench at
    smoke size must run end-to-end and report a NONZERO realized overlap
    ratio (the pipelined PUT really had >1 stripe in flight) plus a sane
    pool hit rate. Throughput floors stay out of tier-1 — this 2-vCPU CI
    host's co-tenant noise would make them flaky; PERF.md carries the
    measured A/B table."""
    from chubaofs_tpu.tools.perfbench import bench_put_pipeline

    out = bench_put_pipeline(str(tmp_path), blob_kb=16, n_puts=2,
                             blob_counts=(1, 4), wire_ms=0)
    assert out["put_overlap_ratio_avg"] > 0, out
    assert out["rpc_pool_hit_rate"] > 0.5, out
    for k in ("put_4b_pipe_pooled_mbps", "put_4b_serial_nopool_mbps",
              "get_4b_pipe_pooled_mbps", "put_pipeline_speedup"):
        assert out[k] > 0, (k, out)


def test_repair_bench_smoke_floor(tmp_path):
    """Tier-1 repair gate (ISSUE 7 satellite): the repair A/B bench at smoke
    size must rebuild the same row count on both arms, report nonzero
    stripes/s, and realize a NONZERO download/decode overlap ratio on the
    windowed arm (the pipeline really overlapped survivor downloads with
    device decode). Speedup floors stay in PERF.md — CI co-tenant noise.
    16 stripes a unit, four gather windows' worth: a unit is re-homed the
    moment it is whole, so the overlap is the window's own (stripe k's decode
    against stripe k+4's download), and 6 stripes showed it only by chance."""
    from chubaofs_tpu.tools.perfbench import bench_repair

    out = bench_repair(str(tmp_path), n_nodes=6, disks_per_node=2,
                       stripes=16, blob_kb=256, wire_ms=2.0, window=4)
    assert out["repair_rows_serial"] > 0, out
    assert out["repair_rows_pipelined"] == out["repair_rows_serial"], out
    assert out["repair_stripes_s_serial"] > 0, out
    assert out["repair_stripes_s_pipelined"] > 0, out
    assert out["repair_speedup"] > 0, out
    assert out["repair_overlap_ratio"] > 0, out
    assert out["repair_bytes_per_shard"] > 0, out


def test_repair_codes_bench_smoke_floor(tmp_path):
    """Tier-1 repair-traffic gate (ISSUE 19 satellite): the RG6P6-vs-EC12P4
    A/B at smoke size must rebuild the same row count on both arms, rebuild
    EVERY RG row through the beta path (single-loss regime by construction:
    one disk per node), and cut bytes-per-repaired-shard by at least the
    25% acceptance floor (geometry predicts 67%; the byte counters are
    deterministic, so unlike stripes/s this IS CI-assertable). Download
    amplification must likewise drop (2x vs 12x predicted). Stripes/s
    floors stay in PERF.md — CI co-tenant noise."""
    from chubaofs_tpu.tools.perfbench import bench_repair_codes

    # eight stripes through a window of four: the later stripes' downloads are
    # launched before the earlier ones' decodes are submitted, and the overlap
    # asserted below is counted from that order (the window's occupancy each
    # time a stripe is taken: the eight blobs alternate between the proxy's two
    # active volumes, so the victim's unit of each has four stripes: 4, 3, 2, 1,
    # a mean of 2.5, i.e. half of the window's three places beyond the stripe
    # itself), not from whether a sub-millisecond decode happened to meet a
    # download on the wall clock
    out = bench_repair_codes(str(tmp_path), stripes=8, blob_kb=60,
                             wire_ms=2.0, window=4)
    assert out["repair_codes_rows_rg"] > 0, out
    assert out["repair_codes_rows_rs"] == out["repair_codes_rows_rg"], out
    assert out["repair_codes_beta_rows"] == out["repair_codes_rows_rg"], out
    assert out["repair_codes_reduction"] >= 0.25, out
    assert out["repair_codes_amp_rg"] < out["repair_codes_amp_rs"], out
    assert out["repair_codes_stripes_s_rg"] > 0, out
    assert out["repair_codes_stripes_s_rs"] > 0, out
    assert out["repair_codes_overlap_rg"] == out["repair_codes_overlap_rs"] == 0.5, out


def test_events_overhead_floor(tmp_path):
    """Tier-1 events gate (ISSUE 13 satellite): emitting 10k journal events
    (ring + rotating JSONL + counters) stays under a generous wall budget,
    and a MiniCluster PUT/GET burst emits ZERO events — the plane records
    transitions, never per-op traffic (the bench itself raises on any
    hot-path event, so this is a correctness gate, not just a floor)."""
    from chubaofs_tpu.tools.perfbench import bench_events
    from chubaofs_tpu.utils import events

    try:
        out = bench_events(str(tmp_path), n_events=10_000, puts=4,
                           blob_kb=32)
    finally:
        events.reset()  # the bench re-pointed the process journal
    assert out["events_hot_path"] == 0, out
    # ~5-15us/event measured on the 2-vCPU dev host; 10x slack for CI
    assert out["events_emit_10k_s"] < 5.0, out
    assert out["events_emit_us_avg"] > 0, out


def test_flightrec_disarmed_overhead_floor(tmp_path):
    """Tier-1 flight-recorder gate (ISSUE 18 satellite): with CFS_FLIGHT
    unset a PUT/GET burst spins no recorder thread and writes no bundle,
    and arming the hook without an alert firing leaves both burst medians
    measured and the bundle dir empty. The bench itself raises on any
    thread or bundle leakage, so this is a correctness gate, not just a
    timing floor."""
    from chubaofs_tpu.tools.perfbench import bench_flightrec

    out = bench_flightrec(str(tmp_path), puts=4, blob_kb=32)
    assert out["flightrec_quiescent_bundles"] == 0, out
    assert out["flightrec_disarmed_med_ms"] > 0, out
    assert out["flightrec_armed_med_ms"] > 0, out


@pytest.mark.slow
def test_perfbench_tool_runs_and_gates(tmp_path):
    # own session so a timeout kill reaps the 7 daemon GRANDCHILDREN too —
    # subprocess.run's kill stops only the direct child, orphaning the
    # ProcCluster (the leak class 426b988 hardened against)
    p = subprocess.Popen(
        [sys.executable, "-m", "chubaofs_tpu.tools.perfbench",
         "--files", "60", "--clients", "2", "--stream-mb", "8",
         "--root", str(tmp_path)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        start_new_session=True)
    try:
        # budget covers the raft microbench + the data-path pipeline A/B
        # (ISSUE 4) + the ProcCluster md/stream/smallfile phases
        stdout, stderr = p.communicate(timeout=540)
    finally:
        try:
            os.killpg(p.pid, signal.SIGKILL)  # idempotent sweep
        except (ProcessLookupError, PermissionError):
            pass
    assert p.returncode == 0, stderr[-2000:]
    line = json.loads(stdout.strip().splitlines()[-1])
    cfg = line["configs"]
    assert line["metric"] == "mdtest_create_ops" and line["unit"] == "ops/s"
    # regression floors (measured ~120/220/60/170 on the dev host)
    assert cfg["create_ops_1c"] > 12, cfg
    assert cfg["stat_ops_1c"] > 25, cfg
    assert cfg["seq_write_mbps"] > 5, cfg
    assert cfg["seq_read_mbps"] > 15, cfg
    assert cfg["smallfile_write_tps"] > 6, cfg
    # raft group-commit microbench floors (measured ~216/169/1530 at this
    # tiny size on the dev host — the 64p config is thread-spawn dominated
    # at 1 op/proposer; full-size numbers live in PERF.md)
    assert cfg["raft_commit_ops_1p"] > 20, cfg
    assert cfg["raft_commit_ops_64p"] > 15, cfg
    assert cfg["raft_commit_ops_8x8"] > 120, cfg
    # batching must actually form batches at 64 concurrent proposers
    assert cfg["raft_commit_batch_64p"] > 1.0, cfg
    # data-path pipeline A/B ran and the pool held its steady-state hits
    # (speedup floors live in PERF.md, not CI — co-tenant noise)
    assert cfg["put_overlap_ratio_avg"] > 0, cfg
    assert cfg["rpc_pool_hit_rate"] > 0.9, cfg
    assert cfg["put_pipeline_speedup_wire"] > 0, cfg


def test_qos_fairness_bench_smoke_floor(tmp_path):
    """Tier-1 fairness gate (ISSUE 14): with the QoS plane armed, the
    ~10x noisy tenant must be CAPPED (throttle counters nonzero) while
    the victim's goodput holds — the two correctness halves of the
    fairness claim. The p99 ratio is reported, not floored, for the same
    co-tenant-noise reason as every other perf number."""
    from chubaofs_tpu.tools.perfbench import bench_qos_fairness

    out = bench_qos_fairness(str(tmp_path), duration=2.5)
    assert out["qos_noisy_throttled"] > 0, out
    assert out["qos_noisy_served"] > 0, out
    assert out["qos_victim_goodput_ratio"] >= 0.7, out
    assert out["qos_victim_p99_mixed_ms"] > 0, out


def test_meta_scale_bench_smoke_floor(tmp_path):
    """Tier-1 metadata scale-out gate (ISSUE 15): the 1 -> 3 -> 4 partition
    growth runs end to end over real metanode daemons and every CORRECTNESS
    gate holds — exact partition counts, contiguous/disjoint ranges, no
    duplicate ino, per-dir census exact (zero created-file loss across the
    live splits), leaders on >=2 metanodes. Wired AFTER the ProcCluster
    phases in perfbench.run() per the PR-8/12 floor-deflation lesson;
    throughput/monotonicity floors stay in PERF.md, not CI (co-tenant
    noise policy — this host has 1 core)."""
    from chubaofs_tpu.tools.perfbench import bench_meta_scale

    out = bench_meta_scale(str(tmp_path), metanodes=4, wire_ms=0.0,
                           dirs=6, seed_files=4, files_per_phase=3,
                           workers_per_partition=2)
    for parts in (1, 3, 4):
        assert out[f"meta_create_ops_{parts}p"] > 0, out
    assert out["meta_leader_nodes"] >= 2, out
    assert out["meta_scale_speedup"] > 0, out


def test_ranged_bench_smoke_floor(tmp_path):
    """Tier-1 ranged-read gate (ISSUE 17): a sub-shard range on an EC12P4
    blob must move fewer backend bytes than the data stripe (the byte-window
    gather claim — floored at <1/4 stripe for a 64 KiB window on a 2 MiB
    blob, against ~1/12 expected), with amp ~1 (window bytes only), the
    degraded arm byte-identical (the phase raises on any mismatch), and the
    cached repeat pass serving from block keys with ZERO backend bytes.
    Latency floors stay in PERF.md, not CI (co-tenant noise policy)."""
    from chubaofs_tpu.tools.perfbench import bench_ranged

    out = bench_ranged(str(tmp_path), blob_mb=2, range_kbs=(64,), gets_per=2)
    assert out["ranged_stripe_frac_64k"] < 0.25, out
    assert 0 < out["ranged_amp_64k"] < 2.0, out
    assert out["ranged_amp_degraded"] > 0, out
    assert out["ranged_decoded_frac_degraded"] < 0.25, out
    assert out["ranged_cached_hits"] > 0, out
    assert out["ranged_cached_backend_bytes"] == 0, out
