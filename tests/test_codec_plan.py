"""What the codec holds instead of recomputing (ISSUE 37): a damage pattern's
GF(2^8) matrix, once a pattern, on the RSKernel; a matrix's launch-ready
form (bits, kron(I_g, .), the kernel's plane-major order, the placement on
the device), once a (content, g), in ops/rs. A held value must equal the
freshly computed one bit for bit, and a decode through the service must
return the plain reference's bytes on a miss and on a hit alike.

The plain reference is benchmark/reference.py + reference_decode.py, loaded
by path: neither imports anything of the program."""

import importlib.util
import json
import os
import sys
import threading

import numpy as np
import pytest

from chubaofs_tpu.codec import pm
from chubaofs_tpu.codec.codemode import get_tactic
from chubaofs_tpu.codec.encoder import lrc_parity_matrix
from chubaofs_tpu.codec.service import CodecService
from chubaofs_tpu.ops import bitmatrix, gf256, pallas_gf, rs
from chubaofs_tpu.utils.exporter import registry

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
K = 16 * 1024  # one shape bucket: no padding copy between a caller and the launch


def _load(name):
    sys.path.insert(0, BENCH)
    try:
        spec = importlib.util.spec_from_file_location(
            "benchmark_" + name, os.path.join(BENCH, name + ".py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod
    finally:
        sys.path.remove(BENCH)


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


# mode -> (configuration that states its geometry and code, how its pattern is drawn)
CONFIGS = {"EC12P4": "az1-ec12p4", "EC16P20L2": "az2-ec16p20l2-azdown", "EC6P3L3": "az3-ec6p3l3"}


def az_dark(t, az):
    """(present, want) of a whole-blob GET with one AZ unreachable."""
    dark = set(t.shards_in_az(az))
    want = [i for i in range(t.N) if i in dark]
    return sorted([i for i in range(t.N + t.M) if i not in dark][: t.N]), want


def drawn(t, seed):
    """A seeded random damage pattern: 1..min(M, 4) global shards lost, the
    survivors a random choice of N of the rest, in a random order."""
    rng = np.random.default_rng([37, t.N, t.M, seed])
    lost = rng.choice(t.N + t.M, size=int(rng.integers(1, min(t.M, 4) + 1)), replace=False)
    alive = [i for i in range(t.N + t.M) if i not in set(lost.tolist())]
    present = rng.permutation(alive)[: t.N].tolist()
    want = sorted(int(i) for i in lost if i < t.N) or [int(lost[0])]
    return present, want


PATTERNS = ([("EC12P4", ("drawn", s)) for s in range(4)]
            + [("EC16P20L2", ("az", 0)), ("EC16P20L2", ("az", 1))]
            + [("EC16P20L2", ("drawn", s)) for s in range(2)]
            + [("EC6P3L3", ("drawn", s)) for s in range(3)])


def pattern_of(mode_name, how):
    t = get_tactic(mode_name)
    return t, (az_dark(t, how[1]) if how[0] == "az" else drawn(t, how[1]))


def ids(p):
    return f"{p[0]}-{p[1][0]}{p[1][1]}"


@pytest.fixture(scope="module")
def refs():
    return _load("reference"), _load("reference_decode")


@pytest.fixture
def fresh(monkeypatch):
    """Nothing held: new kernels (their pattern maps with them) and an empty
    operand map, put back as they were when the test ends."""
    monkeypatch.setattr(rs, "_OPERANDS", rs._Held(rs._OPERANDS.bound))
    rs.get_kernel.cache_clear()
    yield
    rs.get_kernel.cache_clear()


def plan_counts():
    reg = registry("codec")
    return tuple(reg.counter("plan_total", {"result": r}).value for r in ("hit", "miss"))


def fresh_window(kernel, present, want):
    return gf256.gf_matmul(kernel.gen[np.asarray(want), :],
                           gf256.decode_matrix(kernel.gen, present))


# -- a held matrix equals the freshly computed one ------------------------------


@pytest.mark.parametrize("case", PATTERNS, ids=ids)
def test_held_window_matrix_is_the_fresh_one(fresh, case):
    t, (present, want) = pattern_of(*case)
    kernel = rs.get_kernel(t.N, t.M)
    plan, held = kernel.held_window(present, want)
    assert not held
    again, held = kernel.held_window(list(present), tuple(want))
    assert held and again is plan and kernel.window_matrix(present, want) is plan.mat
    assert np.array_equal(plan.mat, fresh_window(kernel, present, want))
    assert plan.mat.dtype == np.uint8 and not plan.mat.flags.writeable
    assert np.array_equal(plan.bits(), bitmatrix.expand_matrix(plan.mat))


@pytest.mark.parametrize("data_only", [False, True])
@pytest.mark.parametrize("case", PATTERNS, ids=ids)
def test_held_repair_matrix_is_the_fresh_one(fresh, case, data_only):
    t, (present, want) = pattern_of(*case)
    bad = [i for i in range(t.N + t.M) if i not in present][: t.M]
    kernel = rs.get_kernel(t.N, t.M)
    mat, survivors, missing = kernel.repair_matrix(bad, data_only)
    assert survivors == [i for i in range(t.N + t.M) if i not in bad][: t.N]
    assert missing == [i for i in sorted(bad) if not data_only or i < t.N]
    expect = fresh_window(kernel, survivors, missing) if missing else np.zeros((0, t.N), np.uint8)
    assert np.array_equal(mat, expect) and not mat.flags.writeable
    (plan, _, _), held = kernel.held_repair(list(reversed(bad)), data_only)
    assert held and plan.mat is mat
    # the lists are the caller's own: editing them does not reach what is held
    survivors.clear(), missing.clear()
    assert kernel.repair_matrix(bad, data_only)[1] != []
    bits, present_idx, missing_idx = kernel.repair_plan(bad, data_only)
    assert np.array_equal(bits, bitmatrix.expand_matrix(expect))
    assert bits.dtype == np.int8 and present_idx.dtype == missing_idx.dtype == np.int32


def _matrices():
    """(name, GF(2^8) matrix): a decode of each geometry, an LRC composed
    generator and a product-matrix parity block."""
    out = []
    for case in (PATTERNS[0], PATTERNS[4], PATTERNS[5], PATTERNS[8]):
        t, (present, want) = pattern_of(*case)
        out.append((ids(case), fresh_window(rs.RSKernel(t.N, t.M), present, want)))
    out.append(("EC6P3L3-composed", lrc_parity_matrix(get_tactic("EC6P3L3"))))
    out.append(("PM-parity", pm.get_kernel(8, 4).parity_mat))
    out.append(("PM-decode", pm.get_kernel(8, 4).decode_matrix([1, 2, 5, 7], [0, 3])))
    return out


MATRICES = _matrices()


@pytest.mark.parametrize("fused", [False, True], ids=["einsum", "fused"])
@pytest.mark.parametrize("name,mat", MATRICES, ids=[n for n, _ in MATRICES])
def test_launch_ready_form_is_the_fresh_one(fresh, monkeypatch, name, mat, fused):
    """Under each lowering's layout: the resident operand of every batch count
    equals kron + permutation of the fresh bits, and is made once."""
    if fused:  # the layout decision only: the operand is placed on the CPU here
        monkeypatch.setattr(rs, "lowering", lambda: rs.FUSED)
    bits = bitmatrix.expand_matrix(mat).astype(np.int8)
    plan = rs.MatrixPlan(mat)
    assert not plan.expanded and np.array_equal(plan.mat, mat)
    seen = set()
    for batch in (1, 2, 3, 4, 8):
        g = pallas_gf.pick_group(batch, *bits.shape) if fused else 1
        assert plan.ready(batch) == (g in seen)
        operand, got_g = plan.operand(batch)
        seen.add(g)
        expect = np.kron(np.eye(g, dtype=np.int8), bits)
        if fused:
            expect = pallas_gf.plane_major(expect)
            stacked, sg = rs.group_stack(bits, batch)
            assert sg == g and np.array_equal(pallas_gf.plane_major(stacked), expect)
        assert got_g == g and operand.dtype == np.int8
        assert np.array_equal(np.asarray(operand), expect)
        # a plan made afresh from an equal matrix finds the SAME resident array
        other = rs.MatrixPlan(np.array(mat))
        assert other.ready(batch) and other.operand(batch)[0] is operand
        assert not other.expanded  # and expanded nothing to find it
    assert len(rs._OPERANDS) == len(seen)


@pytest.mark.parametrize("name,mat", MATRICES[:2] + MATRICES[-2:],
                         ids=[n for n, _ in MATRICES[:2] + MATRICES[-2:]])
def test_resident_plane_major_operand_runs_the_kernel(name, mat):
    """The Pallas kernel (interpret mode) fed the held plane-major form gives
    the GF(2^8) product, as fed byte-major numpy bits."""
    rng = np.random.default_rng(len(name))
    data = rng.integers(0, 256, (2, mat.shape[1], 256), dtype=np.uint8)
    bits = bitmatrix.expand_matrix(mat).astype(np.int8)
    import jax

    resident = jax.device_put(pallas_gf.plane_major(bits))
    got = np.asarray(pallas_gf.gf_matmul_planes(resident, data, interpret=True))
    assert np.array_equal(got, np.asarray(pallas_gf.gf_matmul_bytes_fused(bits, data, interpret=True)))
    assert np.array_equal(got, np.stack([gf256.gf_matmul(mat, d) for d in data]))


def test_hostbatch_takes_bits_or_a_plan(fresh):
    """gf_matmul_hostbatch(mat_bits, shards) keeps its signature: numpy bits go
    through the same operand map (by content), a plan skips the lookup."""
    kernel = rs.get_kernel(6, 3)
    data = np.random.default_rng(3).integers(0, 256, (3, 6, 512), dtype=np.uint8)
    expect = np.stack([gf256.gf_matmul(kernel.gen[6:], d) for d in data])
    assert np.array_equal(rs.gf_matmul_hostbatch(kernel.parity_bits, data), expect)
    assert len(rs._OPERANDS) == 1
    assert np.array_equal(rs.gf_matmul_hostbatch(np.array(kernel.parity_bits), data), expect)
    assert len(rs._OPERANDS) == 1
    assert np.array_equal(rs.gf_matmul_hostbatch(kernel.parity_plan, data), expect)
    assert rs.gf_matmul_hostbatch(kernel.parity_bits, data[:0]).shape == (0, 3, 512)
    assert rs.gf_matmul_hostbatch(np.zeros((0, 48), np.int8), data).shape == (3, 0, 512)


# -- through the service: a miss, then hits, the same bytes ---------------------


@pytest.mark.parametrize("case", PATTERNS, ids=ids)
def test_decode_twice_is_a_miss_then_a_hit_and_the_reference_bytes(fresh, refs, case):
    reference, reference_decode = refs
    t, (present, want) = pattern_of(*case)
    config = _config(CONFIGS[case[0]])
    mode, code = config["modes"][case[0]], config["code"]
    blob = np.random.default_rng([37, len(want)]).bytes(t.N * K)
    stripe = reference.encode(blob, mode, code)
    assert stripe.shape[1] == K
    expect = reference_decode.solve(present, stripe[present], want, mode, code)
    assert np.array_equal(expect, stripe[want])
    svc = CodecService(max_batch=1)
    try:
        before = plan_counts()
        first = np.array(svc.decode_rows(t.N, t.M, present, stripe[present], want).result())
        mid = plan_counts()
        second = np.array(svc.decode_rows(t.N, t.M, present, stripe[present], want).result())
        third = np.array(svc.decode_rows(t.N, t.M, present, stripe[present], want).result())
        after = plan_counts()
    finally:
        svc.close()
    assert np.array_equal(first, expect) and np.array_equal(second, expect)
    assert np.array_equal(third, expect)
    assert (mid[0] - before[0], mid[1] - before[1]) == (0, 1)
    assert (after[0] - mid[0], after[1] - mid[1]) == (2, 0)


def test_matmul_and_encode_count_a_miss_then_hits(fresh):
    """A caller that brings its matrix (PM, LRC) misses once a content, by the
    operand; encode rides the same map."""
    kernel = pm.get_kernel(8, 4)
    mat = kernel.decode_matrix([1, 2, 5, 7], [0, 3])
    data = np.random.default_rng(9).integers(0, 256, (mat.shape[1], K), dtype=np.uint8)
    svc = CodecService(max_batch=1)
    try:
        a = plan_counts()
        got = [np.array(svc.matmul(np.array(mat), data).result()) for _ in range(3)]
        b = plan_counts()
        stripes = [np.array(svc.encode(6, 3, data[:6]).result()) for _ in range(3)]
        c = plan_counts()
    finally:
        svc.close()
    assert all(np.array_equal(g, gf256.gf_matmul(mat, data)) for g in got)
    assert all(np.array_equal(s[6:], gf256.gf_matmul(rs.get_kernel(6, 3).gen[6:], data[:6]))
               for s in stripes)
    assert (b[0] - a[0], b[1] - a[1]) == (2, 1) and (c[0] - b[0], c[1] - b[1]) == (2, 1)


def test_padded_row_rule_cuts_back_and_leaves_the_held_matrix(fresh, refs):
    """PR 36's rule: a row count that has not run rides the narrowest wider
    family that has. The one-row pattern's held matrix keeps its one row."""
    reference, reference_decode = refs
    config = _config("az1-ec12p4")
    mode, code = config["modes"]["EC12P4"], config["code"]
    stripe = reference.encode(np.random.default_rng(36).bytes(12 * K), mode, code)
    two = ([2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13], [0, 1])
    one = ([0, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12], [1])
    svc = CodecService(max_batch=1)
    try:
        assert svc.decode_rows(12, 4, two[0], stripe[two[0]], two[1]).result().shape == (2, K)
        held, _ = rs.get_kernel(12, 4).held_window(*one)
        raw = held.mat.tobytes()
        for _ in range(2):
            got = np.asarray(svc.decode_rows(12, 4, one[0], stripe[one[0]], one[1]).result())
            assert got.shape == (1, K)
            assert np.array_equal(got, reference_decode.solve(one[0], stripe[one[0]], one[1], mode, code))
        assert svc._decode_rows_run[(12, K)] == {2}  # one row rode the two-row family
    finally:
        svc.close()
    again, found = rs.get_kernel(12, 4).held_window(*one)
    assert found and again is held and held.mat.shape == (1, 12) and held.mat.tobytes() == raw
    with pytest.raises(ValueError):
        held.mat[0, 0] ^= 1


def test_maps_stay_within_their_bound_and_an_evicted_pattern_recomputes(fresh, monkeypatch):
    kernel = rs.get_kernel(12, 4)
    kernel._window.bound = kernel._repair.bound = 4
    monkeypatch.setattr(rs, "_OPERANDS", rs._Held(4))
    t = get_tactic("EC12P4")
    patterns = [drawn(t, s) for s in range(100, 110)]
    firsts = []
    for present, want in patterns:
        plan, held = kernel.held_window(present, want)
        assert not held
        firsts.append(plan.mat.copy())
        plan.operand(1)
        kernel.repair_matrix(want)
        assert len(kernel._window) <= 4 and len(kernel._repair) <= 4 and len(rs._OPERANDS) <= 4
    assert len(kernel._window) == len(rs._OPERANDS) == 4
    # the newest four are held, the oldest went and comes back equal
    assert all(kernel.held_window(*p)[1] for p in patterns[-4:])
    plan, held = kernel.held_window(*patterns[0])
    assert not held and np.array_equal(plan.mat, firsts[0])
    assert np.array_equal(plan.mat, fresh_window(kernel, *patterns[0]))
    assert not plan.ready(1)
    assert np.array_equal(np.asarray(plan.operand(1)[0]), bitmatrix.expand_matrix(plan.mat))
    assert len(kernel._window) == len(rs._OPERANDS) == 4


def test_bounded_map_under_sixteen_writers():
    """Every put is answered with a value of ITS key, whichever writer got there
    first, and the map ends at its bound (a put trims under the lock)."""
    held = rs._Held(8)
    wrong: list = []
    gate = threading.Barrier(16)

    def writer(i):
        gate.wait()
        for n in range(2000):
            key = (n + i) % 24
            got = held.put(key, (key, i))
            if got[0] != key or (held.get(key) or got)[0] != key:
                wrong.append((i, n, got))

    threads = [threading.Thread(target=writer, args=(i,)) for i in range(16)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for th in threads:
            th.start()
        for th in threads:
            th.join(60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(th.is_alive() for th in threads) and not wrong and len(held) == 8


def test_sixteen_threads_submit_one_new_pattern_at_once(fresh, refs):
    reference, reference_decode = refs
    config = _config("az2-ec16p20l2-azdown")
    mode, code = config["modes"]["EC16P20L2"], config["code"]
    t = get_tactic("EC16P20L2")
    present, want = az_dark(t, 0)
    stripes = [reference.encode(np.random.default_rng([16, i]).bytes(16 * K), mode, code)
               for i in range(16)]
    svc = CodecService(max_batch=4)
    gate = threading.Barrier(16)
    got: list = [None] * 16

    def reader(i):
        gate.wait()
        got[i] = np.array(svc.decode_rows(t.N, t.M, present, stripes[i][present], want).result())

    before = plan_counts()
    threads = [threading.Thread(target=reader, args=(i,)) for i in range(16)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)  # hand the interpreter lock over inside the misses
    try:
        for th in threads:
            th.start()
        for th in threads:
            th.join(60)
    finally:
        sys.setswitchinterval(interval)
        svc.close()
    assert not any(th.is_alive() for th in threads)
    for i in range(16):
        assert np.array_equal(got[i], stripes[i][want]), i
    # every thread ends up with the ONE held plan; at least one of them made it
    assert len(rs.get_kernel(t.N, t.M)._window) == 1
    after = plan_counts()
    assert after[0] - before[0] + after[1] - before[1] == 16 and after[1] > before[1]


# -- the per-layer metric that reads the counter --------------------------------

GET_CELLS = ["az1.get16m-nodedown", "az2.get16m-azdown", "az1.get16m-rebuild"]


def test_hit_share_layer_is_the_benchmarks_entry_and_reads_nothing_without_the_counter():
    with open(os.path.join(BENCH, "layers", "get_codec_plan_hit_share.json")) as f:
        spec = json.load(f)
    with open(os.path.join(BENCH, "..", "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(e for e in bench["per_layer"] if e["name"] == "get_codec_plan_hit_share")
    assert entry == {k: spec[k] for k in entry}
    assert entry["workloads"] == GET_CELLS and entry["moves"] == "get_MBps"
    assert entry["layer"] in {e["layer"] for e in bench["per_layer"] if e is not entry}
    reducer = _load(os.path.join("reducers", spec["reducer"]))

    def reduce(before, after):
        return reducer.reduce({"snap0": {"counters": before}, "snap1": {"counters": after}},
                              spec["params"])

    hit, miss = 'cfs_codec_plan_total{result="hit"}', 'cfs_codec_plan_total{result="miss"}'
    assert reduce({hit: 10.0, miss: 4.0}, {hit: 109.0, miss: 5.0}) == 0.99
    assert reduce({}, {hit: 7.0}) == 1.0
    # the parent of the PR that brought the counter: nothing to read, and no raise
    assert reduce({"cfs_codec_jobs_total": 1.0}, {"cfs_codec_jobs_total": 90.0}) is None
