"""A codec job's bytes live in ONE pooled, bucket-wide slot from submission to
delivery (codec/service.py): nothing past a job's true length means anything,
a buffer goes back to the pool only when its last view is dead, the shapes the
host boundary sees are what they were, and the pool's idle bytes are bounded."""
import gc
import sys
import threading
import time
import types
import weakref

import numpy as np
import pytest

from chubaofs_tpu.blobstore.access import Access
from chubaofs_tpu.codec import pm
from chubaofs_tpu.codec.codemode import CodeMode, get_tactic
from chubaofs_tpu.codec.encoder import lrc_parity_matrix
from chubaofs_tpu.codec.service import CodecService, _address, _lent, bucket_len
from chubaofs_tpu.ops import gf256, rs
from chubaofs_tpu.utils.exporter import registry


def _taken(kind: str, result: str) -> float:
    return registry("codec").counter("buffer_total", {"kind": kind, "result": result}).value


def _generator(parity: np.ndarray) -> np.ndarray:
    """[I; parity]: what gf256.encode_numpy takes for a systematic code."""
    return np.concatenate([np.eye(parity.shape[1], dtype=np.uint8), np.asarray(parity, np.uint8)])


def _dirty(svc: CodecService, rng, n: int, k: int, rows: int = 0) -> np.ndarray:
    """A lent slot, every byte of it random: what a reused buffer looks like."""
    slot = svc.slot(n, k, rows)
    slot[:] = rng.integers(0, 256, slot.shape, dtype=np.uint8)
    return slot


# -- (a) columns at and past k are nobody's ------------------------------------

def _run_encode(svc, rng, k, lent):
    n, m = 6, 3
    data = rng.integers(0, 256, (n, k), dtype=np.uint8)
    want = gf256.encode_numpy(gf256.systematic_generator(n, m), data)
    slot = _dirty(svc, rng, n, k, m)
    if lent:
        slot[:n, :k] = data
        got = svc.encode(n, m, slot[:n, :k]).result(60)
        assert _lent(got) is _lent(slot)  # the stripe is a view of the slot that was lent
    else:
        del slot  # back to the pool, dirty: the copy-in path reuses it
        got = svc.encode(n, m, data).result(60)
    return got, want


def _run_tactic(mode):
    def run(svc, rng, k, lent):
        t = get_tactic(mode)
        k = t.shard_size(k * t.N)  # a length the tactic allows (PM: a multiple of sub_units)
        data = rng.integers(0, 256, (t.N, k), dtype=np.uint8)
        if t.is_regenerating:
            sub = data.reshape(t.N * t.sub_units, -1)
            want = gf256.encode_numpy(_generator(pm.get_kernel(t.total, t.N).parity_mat),
                                      sub).reshape(t.total, k)
        else:
            parity = lrc_parity_matrix(t) if t.L else gf256.systematic_generator(t.N, t.M)[t.N:]
            want = gf256.encode_numpy(_generator(parity), data)
        slot = _dirty(svc, rng, t.N, k, t.total - t.N)
        if lent:
            slot[: t.N, :k] = data
            data = slot[: t.N, :k]
        del slot
        return svc.encode_tactic(t, data).result(60), want
    return run


def _run_decode_rows(svc, rng, k, lent):
    n, m = 6, 3
    stripe = gf256.encode_numpy(gf256.systematic_generator(n, m),
                                rng.integers(0, 256, (n, k), dtype=np.uint8))
    present, need = [0, 2, 3, 5, 6, 8], [1, 4]
    _dirty(svc, rng, n, k)  # dropped at once: the next taker of the shape gets it
    survivors = svc.slot_of([stripe[i].tobytes() for i in present]) if lent else stripe[present]
    return svc.decode_rows(n, m, present, survivors, need).result(60), stripe[need]


def _run_reconstruct(svc, rng, k, lent):
    n, m = 6, 3
    stripe = gf256.encode_numpy(gf256.systematic_generator(n, m),
                                rng.integers(0, 256, (n, k), dtype=np.uint8))
    bad = [1, 7] if lent else [0, 4, 8]
    holed = stripe.copy()
    holed[bad] = rng.integers(0, 256, (len(bad), k), dtype=np.uint8)
    _dirty(svc, rng, n, k)  # the slot reconstruct will take
    return svc.reconstruct(n, m, holed, bad).result(60), stripe


OPS = {"encode": _run_encode, "tactic_rs": _run_tactic(CodeMode.EC6P3),
       "tactic_lrc": _run_tactic(CodeMode.EC6P3L3), "tactic_pm": _run_tactic(CodeMode.RG4P4),
       "decode_rows": _run_decode_rows, "reconstruct": _run_reconstruct}


@pytest.mark.parametrize("lent", [True, False], ids=["lent", "copied_in"])
@pytest.mark.parametrize("k", [16 * 1024, 20_001], ids=["k_is_bucket", "k_padded"])
@pytest.mark.parametrize("op", OPS)
def test_garbage_past_k_changes_no_byte(op, k, lent):
    """Every op, through a slot whose every byte (the columns at and past k and
    the result rows too) was random before the job's rows were written: the
    result is gf256's, byte for byte. Nobody zero-fills, and nobody needs to."""
    svc = CodecService()
    try:
        got, want = OPS[op](svc, np.random.default_rng(k + lent), k, lent)
        assert got.shape == want.shape and got.dtype == np.uint8
        assert np.array_equal(got, want)
    finally:
        svc.close()


@pytest.mark.parametrize("mode,size", [
    (CodeMode.EC3P3, 4096), (CodeMode.EC3P3, 49_152), (CodeMode.EC3P3, 50_001),
    (CodeMode.EC12P4, 4 << 20), (CodeMode.EC12P4, (4 << 20) - 7), (CodeMode.EC12P4, 1_200_001),
    (CodeMode.EC6P3L3, 777_777), (CodeMode.EC6P3L3, 6 * 2048 + 1), (CodeMode.RG4P4, 90_001),
])
def test_encode_blob_writes_the_rows_the_zero_filled_matrix_held(monkeypatch, mode, size):
    """Access._encode_blob fills a lent (dirty) slot row by row: the rows it
    submits equal the old np.zeros((N, shard_len)) + flat copy, the blob's
    tail and the rows past it zeroed, and they ARE the slot (no copy-in)."""
    t = get_tactic(mode)
    blob = np.random.default_rng(size).integers(0, 256, size, dtype=np.uint8).tobytes()
    shard_len = t.shard_size(size)
    want = np.zeros((t.N, shard_len), np.uint8)
    want.reshape(-1)[:size] = np.frombuffer(blob, np.uint8)
    svc = CodecService()
    seen = []
    monkeypatch.setattr(svc, "encode_tactic", lambda tt, rows: seen.append(rows))
    try:
        _dirty(svc, np.random.default_rng(1), t.N, shard_len, t.total - t.N)
        Access._encode_blob(types.SimpleNamespace(codec=svc), t, blob)
    finally:
        svc.close()
    (rows,) = seen
    assert np.array_equal(rows, want)
    owner = _lent(rows)
    assert owner is not None and _address(rows) == _address(owner)
    assert owner.size == t.total * bucket_len(shard_len)  # room for every parity row


def test_a_lent_slot_without_room_for_the_parity_is_copied_not_overrun():
    """An encode over rows lent with room for ONE parity row of three: the job
    takes a slot of its own (counted), and the stripe is still right."""
    n, m, k = 6, 3, 20_001
    data = np.random.default_rng(3).integers(0, 256, (n, k), dtype=np.uint8)
    svc = CodecService()
    try:
        rows = svc.slot(n, k, 1)[:n, :k]
        rows[:] = data
        before = _taken("slot", "fresh") + _taken("slot", "reused")
        got = svc.encode(n, m, rows).result(60)
        assert _taken("slot", "fresh") + _taken("slot", "reused") == before + 1
        assert _lent(got) is not _lent(rows)
        assert np.array_equal(got, gf256.encode_numpy(gf256.systematic_generator(n, m), data))
    finally:
        svc.close()


@pytest.mark.parametrize("count", [1, 3], ids=["one_job", "stacked"])
def test_decoded_rows_are_views_of_the_fetched_array_and_the_slot_goes_back(count):
    """A decode's future gets its rows where the fetch put them (no copy into
    the slot): they outlive the slot, which is idle again once the submitter
    has let go of its survivors, and they are cut to the rows asked for."""
    n, m, k = 6, 3, 20_001
    rng = np.random.default_rng(count)
    present, need = [0, 2, 3, 5, 6, 8], [1, 4]
    svc = CodecService(max_batch=count, max_wait_ms=20_000.0 if count > 1 else 0.0)
    try:
        stripes = [gf256.encode_numpy(gf256.systematic_generator(n, m),
                                      rng.integers(0, 256, (n, k), dtype=np.uint8))
                   for _ in range(count)]
        survivors = [svc.slot_of([s[i] for i in present]) for s in stripes]
        owners = [weakref.ref(_lent(v)) for v in survivors]
        futs = [svc.decode_rows(n, m, present, v, need) for v in survivors]
        got = [f.result(60) for f in futs]
        assert all(_lent(g) is None and g.shape == (2, k) for g in got)
        svc.max_wait = 0.0
        svc.encode(3, 3, np.zeros((3, 100), np.uint8)).result(60)  # the dispatcher lets go of the batch
        del survivors, futs
        gc.collect()
        assert all(o() is None for o in owners)
        assert all(np.array_equal(g, s[need]) for g, s in zip(got, stripes))
    finally:
        svc.close()


# -- (b) a buffer is its last view's -------------------------------------------

def _fake_matmul(calls: list):
    """rs.gf_matmul_hostbatch's shape contract without the math: records what
    it was handed and returns zeros of the result's shape."""
    def mm(plan, shards):
        calls.append((shards.shape, shards.dtype, shards.flags.c_contiguous))
        return np.zeros((*shards.shape[:-2], plan.shape[0] // rs.BITS, shards.shape[-1]), np.uint8)
    return mm


@pytest.mark.parametrize("batch", [1, 3], ids=["one_job_batches", "stacked_batches"])
def test_a_kept_result_survives_64_later_batches_and_is_reused_only_when_dropped(batch):
    n, m, k = 6, 3, 20_001
    gen = gf256.systematic_generator(n, m)
    rng = np.random.default_rng(batch)
    # a hold, so that every drain closes with exactly `batch` jobs
    svc = CodecService(max_batch=batch, max_wait_ms=20_000.0 if batch > 1 else 0.0)
    try:
        def one_batch():
            datas = [rng.integers(0, 256, (n, k), dtype=np.uint8) for _ in range(batch)]
            futs = [svc.encode(n, m, d) for d in datas]
            return datas, [f.result(60) for f in futs]

        datas, kept = one_batch()
        want = [gf256.encode_numpy(gen, d) for d in datas]
        owners = [weakref.ref(_lent(r)) for r in kept]
        held = {_address(r) for r in kept}
        later = set()  # this service's own slots (the counter is the process's)
        for _ in range(64):
            datas, results = one_batch()
            assert not held & {_address(r) for r in results}  # never a kept job's memory
            assert all(np.array_equal(r, gf256.encode_numpy(gen, d))
                       for r, d in zip(results, datas))
            later |= {_address(r) for r in results}
            del results
        # the later batches mapped one set of slots of their own (a set more each
        # time the dispatcher, or the backend's last launch, had not let go of a
        # batch yet: timing, a few at most), and took the pool's ever after
        assert batch <= len(later) <= 4 * batch
        assert all(np.array_equal(r, w) for r, w in zip(kept, want))
        assert all(o() is not None for o in owners)
        # the dispatcher lets go of a batch before it drains the next: after a job
        # of ANOTHER shape, every slot of this shape but the kept ones is idle
        svc.max_wait = 0.0
        svc.encode(3, 3, np.zeros((3, 100), np.uint8)).result(60)
        row = kept[0][2]  # one ROW of a stripe (what a straggling shard write holds)
        del kept
        gc.collect()
        assert [o() is None for o in owners] == [False] + [True] * (batch - 1)
        assert np.array_equal(row, want[0][2])
        del row
        gc.collect()
        assert all(o() is None for o in owners)
        fresh, reused = _taken("slot", "fresh"), _taken("slot", "reused")
        again = [svc.slot(n, k, m) for _ in range(batch)]
        assert {_address(s) for s in again} == held  # last back, first out
        assert (_taken("slot", "fresh"), _taken("slot", "reused")) == (fresh, reused + batch)
    finally:
        svc.close()


def test_a_cancelled_jobs_slot_comes_back(monkeypatch):
    n, m, k = 6, 3, 20_001
    sound, entered, release = rs.gf_matmul_hostbatch, threading.Event(), threading.Event()

    def gated(plan, shards):
        entered.set()
        assert release.wait(60)
        return sound(plan, shards)

    monkeypatch.setattr(rs, "gf_matmul_hostbatch", gated)
    data = np.random.default_rng(5).integers(0, 256, (n, k), dtype=np.uint8)
    svc = CodecService()
    try:
        first = svc.encode(n, m, data)
        assert entered.wait(60)  # the dispatcher is inside the first job's batch
        dropped = svc.encode(n, m, data)
        assert dropped.cancel()
        release.set()
        assert np.array_equal(first.result(60), gf256.encode_numpy(
            gf256.systematic_generator(n, m), data))
        del first, dropped
        deadline = time.monotonic() + 30
        while svc._pool.idle_bytes() < 2 * (n + m) * bucket_len(k) and time.monotonic() < deadline:
            # the dispatcher drops the cancelled job at its next drain; jax lets go of
            # the last launch's input at the next launch or collection
            time.sleep(0.01)
            gc.collect()
        assert svc._pool.idle_bytes() == 2 * (n + m) * bucket_len(k)
    finally:
        release.set()
        svc.close()


# -- (c) the host boundary sees the shapes it saw ------------------------------

GEOMETRY = {  # mode, shard bytes of a 4 MiB blob, bucket
    "az1": (CodeMode.EC12P4, 349_526, 524_288),
    "az3": (CodeMode.EC6P3L3, 699_051, 1_048_576),
    "az2": (CodeMode.EC16P20L2, 262_144, 262_144),
}


@pytest.mark.parametrize("count", [1, 2, 5])
@pytest.mark.parametrize("cell", GEOMETRY)
def test_the_batch_handed_to_the_host_boundary_is_todays_shape(monkeypatch, cell, count):
    """(B, N, bucket) uint8, C-contiguous, for B = 1, 2, 5, whether the rows came
    in lent slots or with the caller: no new compiled program."""
    mode, k, kb = GEOMETRY[cell]
    t = get_tactic(mode)
    assert (t.shard_size(4 << 20), bucket_len(k)) == (k, kb)
    calls: list = []
    monkeypatch.setattr(rs, "gf_matmul_hostbatch", _fake_matmul(calls))
    svc = CodecService(max_batch=count, max_wait_ms=20_000.0 if count > 1 else 0.0)
    try:
        for lent in (True, False):
            rows = [svc.slot(t.N, k, t.total - t.N)[: t.N, :k] if lent
                    else np.zeros((t.N, k), np.uint8) for _ in range(count)]
            for f in [svc.encode_tactic(t, r) for r in rows]:
                assert f.result(60).shape == (t.total, k)
    finally:
        svc.close()
    assert calls == [((count, t.N, kb), np.dtype(np.uint8), True)] * 2


# -- (d) the pool's idle bytes are bounded --------------------------------------

SHAPES = [(3, 3, 1_000), (3, 3, 20_000), (3, 3, 40_000), (6, 3, 1_000), (6, 3, 20_000), (6, 3, 40_000)]


@pytest.mark.parametrize("max_batch", [1, 2, 4])
def test_the_pools_idle_bytes_stay_under_the_bound_over_200_jobs_of_six_shapes(monkeypatch, max_batch):
    """Idle bytes <= 4 * max_batch * the largest slot seen, whatever is kept
    and dropped when, and take never fails."""
    monkeypatch.setattr(rs, "gf_matmul_hostbatch", _fake_matmul([]))
    rng = np.random.default_rng(max_batch)
    svc = CodecService(max_batch=max_batch)
    pool = svc._pool
    largest, high = 0, 0
    try:
        kept: list = []
        for i in range(200):
            n, m, k = SHAPES[int(rng.integers(len(SHAPES))) if i >= 40 else i % 2]
            largest = max(largest, (n + m) * bucket_len(k))
            kept.append(svc.encode(n, m, np.zeros((n, k), np.uint8)).result(60))
            if len(kept) >= 64:  # 64 results die at once: far more than the bound
                del kept[:]
            high = max(high, pool.idle_bytes())
            assert high <= 4 * max_batch * largest
            assert pool._idle_bytes == sum(len(raw) for idle in pool._idle.values() for raw in idle)
        assert high > 2 * max_batch * largest  # the bound was reached for, not idled under
        # and the pool still serves a shape without mapping anything
        n, m, k = SHAPES[-1]
        a, b = svc.slot(n, k, m), svc.slot(n, k, m)
        del a, b
        fresh = _taken("slot", "fresh")
        svc.slot(n, k, m)
        assert _taken("slot", "fresh") == fresh
    finally:
        svc.close()


def test_oldest_shapes_are_unmapped_first():
    svc = CodecService(max_batch=1)
    pool = svc._pool
    try:
        big = (6 + 3) * bucket_len(40_000)  # the largest slot: the bound is 4 of it
        old = [svc.slot(3, 1_000, 3) for _ in range(8)]  # 8 x 96 KiB of the OLDEST shape
        new = [svc.slot(6, 40_000, 3) for _ in range(4)]
        del old, new
        # 8 x 96 KiB over the bound: every buffer of the oldest shape went, the newest stayed
        assert pool.idle_bytes() == 4 * big
        assert [len(v) for v in pool._idle.values()] == [4]
    finally:
        svc.close()


# -- many submitters, one pool ---------------------------------------------------

def test_sixteen_threads_each_get_their_own_bytes_back():
    """More threads than cores, a short switch interval, slots taken and dying
    on every thread at once: a buffer handed to two jobs, or taken back under
    a live view, would show as a stripe that is not its own data's."""
    n, m = 3, 3
    gen = gf256.systematic_generator(n, m)
    svc = CodecService()
    wrong: list = []
    stop = time.monotonic() + 4.0

    def work(seed: int):
        rng = np.random.default_rng(seed)
        last = None
        while time.monotonic() < stop and not wrong:
            k = int(rng.choice([5_000, 16_384, 20_001]))
            rows = svc.slot(n, k, m)[:n, :k]
            rows[:] = rng.integers(0, 256, (n, k), dtype=np.uint8)
            data = rows.copy()
            got = svc.encode(n, m, rows if seed % 2 else data).result(60)
            if last is not None and not np.array_equal(last[0], last[1]):
                wrong.append(("kept", seed))
            if not np.array_equal(got, gf256.encode_numpy(gen, data)):
                wrong.append(("fresh", seed))
            last = (got, got.copy())  # held across the next job

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(s,)) for s in range(16)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(60)
        assert not any(th.is_alive() for th in threads)
    finally:
        sys.setswitchinterval(interval)
        svc.close()
    assert not wrong
