"""CodecService — the batching device sidecar for erasure-coding math.

Reference analog: the access layer encodes each blob inline on the CPU
(stream_put.go:143 `encoder.Encode`) and blobnode workers reconstruct per-task
(work_shard_recover.go:422). On TPU, per-blob dispatch would waste the chip:
each call pays host->device latency, and small stripes underfill the MXU. This
service is the TPU-native replacement:

  * callers submit encode/repair jobs (numpy matrices) and get futures back;
  * a dispatcher thread drains the queue, groups jobs by (matrix, k-bucket),
    runs ONE fused-kernel call over the group's (B, n, bucket) batch, then
    hands each job its rows back;
  * shard lengths are bucketed to powers of two (>= 16 KiB) so the jit cache
    stays small and the MXU sees few distinct shapes;
  * a job's bytes live in ONE pooled, bucket-wide buffer (a slot) from
    submission to delivery. Nobody pads: a GF matmul is column by column and
    every result is cut back to the job's true length, so the columns past
    it are never written, zeroed or read. The caller that builds a job's
    input asks for the slot (`slot` / `slot_of`) and writes its rows there;
    one that brings its own array has them copied in, once (the snapshot).
    One job is launched where it lies; two and more are copied into a pooled
    batch buffer (the dispatcher stacks). An encode's fetched parity rows go
    into the tail rows of the job's own slot and the future gets a VIEW of
    the slot, the stripe, so nothing is concatenated; a decode's future gets
    its rows as a view of the fetched array, so nothing is copied at all. A
    buffer is owned by whoever still holds an array that views it: it
    returns to the pool when the last such array dies (`_Pool`), never by a
    caller's say-so.
    cfs_codec_buffer_total{kind="slot"|"batch", result="reused"|"fresh"}
    counts every buffer taken;
  * the lowering is the process's, decided once from the resolved backend
    (rs.lowering): the compiled fused kernel on a TPU, the XLA einsum when CPU
    was asked for (tests). A backend that fails to initialise fails the job;
    nothing falls back. cfs_codec_lowering_jobs_total{lowering=...} says which
    one did the math.

Batching is group commit, like the reference's proxy-side volume-allocation
batching but for math instead of metadata: the one dispatcher starts the moment
it has a job and takes with it everything that queued while the last batch ran,
so batches grow with load by themselves and an idle service adds no wait. It
never sleeps on a timer with a job in hand, unless a caller sets a hold
(`max_wait` > 0: wait up to that long for `max_batch` jobs), the instrument of
warm-ups and tests that need a batch of an exact count.
cfs_codec_batch_close_total{close="empty"|"full"|"held"} says how each batch closed.
"""

from __future__ import annotations

import math
import mmap
import queue
import threading
import time
import weakref
from collections import OrderedDict, deque
from concurrent.futures import Future, InvalidStateError
from dataclasses import dataclass, field

import numpy as np

from chubaofs_tpu.blobstore import trace
from chubaofs_tpu.ops import rs
from chubaofs_tpu.utils.exporter import BATCH_BUCKETS, registry
from chubaofs_tpu.utils.locks import SanitizedLock

MIN_BUCKET = 16 * 1024


def bucket_len(k: int) -> int:
    """Round a shard length up to the service's shape bucket."""
    b = MIN_BUCKET
    while b < k:
        b *= 2
    return b


class _ChainFuture(Future):
    """Wrapper future whose cancel() propagates to the upstream codec job,
    so a caller holding only the composed LRC result (encode_tactic) can
    still drop the queued device work (access pipeline aborts)."""

    def __init__(self, upstream: Future):
        super().__init__()
        self._upstream = upstream

    def cancel(self) -> bool:
        self._upstream.cancel()  # best-effort: running jobs finish
        return super().cancel()


@dataclass
class _Job:
    kind: str  # "encode" | "matmul"
    n: int  # input rows: buf[:n] is what the launch reads
    rows: int  # result rows the caller gets (a padded decode matrix makes more)
    buf: np.ndarray  # (>= n, kb) uint8: the job's pooled slot
    k: int  # true shard length (nothing at or past this column means anything)
    kb: int  # bucket_len(k): the slot's width
    # the future gets a stripe: the result rows are written to buf[n : n + rows]
    # and buf[: n + rows] is the result. Else the result rows alone, a view of
    # the fetched array
    whole: bool = False
    future: Future = field(default_factory=Future)
    # the job's matrix as the launch needs it (rs.MatrixPlan): its key groups
    # the batch, its operand is resident on the device after the first batch
    plan: rs.MatrixPlan | None = None
    # the matrix came from a held damage pattern, or with the caller: the
    # submitting thread computed nothing for it (cfs_codec_plan_total)
    held: bool = True
    # the SUBMITTER's trace span (if any): the dispatcher attributes the
    # job's queue wait and its batch's stack/matmul intervals back onto it
    # as named stages, so a PUT's critical-path report splits encode wait
    span: object | None = None
    t_submit: float = 0.0  # perf_counter at _submit: codec.queue_wait starts


class _Raw(mmap.mmap):
    """A pooled buffer's memory: anonymous pages, mapped at first touch and
    kept mapped while the pool or a borrower holds it. The type is the mark
    by which the service knows an array it lent (`_lent`)."""

    def __new__(cls, nbytes: int):
        raw = super().__new__(cls, -1, nbytes,
                              flags=mmap.MAP_PRIVATE | mmap.MAP_ANONYMOUS)
        if nbytes >= 1 << 22:  # numpy's own rule for what it allocates
            raw.madvise(mmap.MADV_HUGEPAGE)
        return raw


def _address(a: np.ndarray) -> int:
    return a.__array_interface__["data"][0]


def _lent(a: np.ndarray) -> np.ndarray | None:
    """The owning array of the pooled buffer `a` views, or None."""
    owner = a.base
    raw = getattr(getattr(owner, "base", None), "obj", None)
    return owner if isinstance(raw, _Raw) else None


class _Pool:
    """Buffers by shape, lent as plain arrays and taken back by reference
    counting: a buffer is idle again when the array `take` made and every
    view of it are dead (numpy views hold their owner), so a straggler that
    still reads a result keeps exactly its own bytes. `take` never blocks
    and never fails: with nothing idle of a shape it maps a new buffer. Idle
    bytes are bounded by 4 * max_batch * the largest slot seen (a queue's
    worth of jobs in flight, as many results still being read, and a batch
    buffer, which is under two max_batch slots); past that the shapes taken
    longest ago are unmapped first. What dies between two takes
    waits, uncounted, for the next."""

    def __init__(self):
        self._lock = SanitizedLock(name="codec.pool")
        # shape -> idle memory, the shape taken longest ago first
        self._idle: OrderedDict[tuple, list[_Raw]] = OrderedDict()
        self._idle_bytes = 0
        self._largest_slot = 0
        self._limit = 0
        # what the finalizers hand back: they run wherever the last view
        # dies (any thread, any moment), so they take no lock and only append
        self._back: deque[tuple[tuple, _Raw]] = deque()
        reg = registry("codec")
        self._taken = {(kind, result): reg.counter(
            "buffer_total", {"kind": kind, "result": result})
            for kind in ("slot", "batch") for result in ("reused", "fresh")}

    def take(self, kind: str, shape: tuple, max_batch: int) -> np.ndarray:
        nbytes = math.prod(shape)
        with self._lock:
            if kind == "slot":
                self._largest_slot = max(self._largest_slot, nbytes)
            self._limit = 4 * max_batch * self._largest_slot
            self._sweep_locked()
            idle = self._idle.get(shape)
            raw = idle.pop() if idle else None
            if raw is not None:
                self._idle_bytes -= nbytes
                self._idle.move_to_end(shape)
            self._trim_locked()
        self._taken[kind, "fresh" if raw is None else "reused"].add()
        if raw is None:
            raw = _Raw(nbytes)
        owner = np.frombuffer(raw, np.uint8)
        weakref.finalize(owner, self._back.append, (shape, raw)).atexit = False
        return owner.reshape(shape)

    def _sweep_locked(self) -> None:
        while self._back:
            shape, raw = self._back.popleft()
            self._idle.setdefault(shape, []).append(raw)
            self._idle_bytes += len(raw)

    def _trim_locked(self) -> None:
        while self._idle_bytes > self._limit:
            shape, idle = next(iter(self._idle.items()))
            if idle:
                self._idle_bytes -= len(idle.pop())
            if not idle:
                del self._idle[shape]

    def idle_bytes(self) -> int:
        with self._lock:
            self._sweep_locked()
            self._trim_locked()
            return self._idle_bytes


class CodecService:
    """Queue -> bucket-wide device batches -> futures. Thread-safe, one device stream."""

    def __init__(self, max_batch: int = 32, max_wait_ms: float = 0.0,
                 mesh=None, mesh_interpret: bool = False):
        """mesh: optional jax.sharding.Mesh (dp, sp) — drained batches then
        run through parallel.mesh.sharded_gf_matmul instead of the single-
        device path, which takes the whole blobstore data plane (access
        PUT/GET, scheduler bulk repair) multi-chip without any caller
        change (SURVEY §7 step 6). mesh_interpret forces the Pallas kernel
        in interpret mode on CPU meshes (the dryrun/test path)."""
        self.max_batch = max_batch
        self.max_wait = max_wait_ms / 1e3
        self.mesh = mesh
        self._mesh_mm = None
        if mesh is not None:
            from chubaofs_tpu.parallel.mesh import sharded_gf_matmul

            self._mesh_mm = sharded_gf_matmul(mesh, interpret=mesh_interpret)
        self._q: queue.Queue[_Job | None] = queue.Queue()
        self._thread = threading.Thread(target=self._run, daemon=True, name="codec-svc")
        self._started = False
        self._closed = False
        self._lock = SanitizedLock(name="codec.lifecycle")
        # dispatcher observability: how well jobs coalesce into device batches
        # (same counter shape as MultiRaft.drain_stats for the raft drain).
        # The codec role registry (cfs_codec_*) is the primary surface; this
        # dict is the legacy view, mutated only under _stats_lock so readers
        # get consistent snapshots (stats_snapshot).
        self.stats = {"batches": 0, "jobs": 0, "max_batch": 0}
        self._stats_lock = SanitizedLock(name="codec.stats")
        # row counts decode_rows has run, by (survivors, bucket): the families
        # of compiled decode programs this process holds
        self._decode_rows_run: dict[tuple[int, int], set[int]] = {}
        self._pool = _Pool()

    def _ensure_started(self):
        with self._lock:
            if self._closed:
                raise RuntimeError("CodecService is closed")
            if not self._started:
                self._thread.start()
                self._started = True

    # -- public API --------------------------------------------------------

    def slot(self, n: int, k: int, rows: int = 0) -> np.ndarray:
        """Lend the buffer of a job of n input rows of k bytes: a
        (n + rows, bucket_len(k)) uint8 array out of the pool, its bytes
        whatever the last borrower left. An encode asks for room for its
        `rows` parity rows, and its stripe is then a view of the same slot.
        The caller writes slot[:n, :k], all of it, and submits that view as
        the job's data: the job runs where the bytes lie. Nothing at or past
        column k needs writing. Nothing is given back by hand: the slot is
        the pool's again when the last array viewing it (this one, the
        stripe, a row of it) is dead."""
        return self._pool.take("slot", (n + rows, bucket_len(k)), self.max_batch)

    def slot_of(self, payloads) -> np.ndarray:
        """The equal-length byte strings `payloads` (a stripe's survivors, in
        the decode's row order) as the rows of a lent slot: the (n, k) data
        view to submit."""
        n, k = len(payloads), len(payloads[0])
        data = self.slot(n, k)[:, :k]
        for row, payload in zip(data, payloads):
            row[:] = (payload if isinstance(payload, np.ndarray)
                      else np.frombuffer(payload, np.uint8))
        return data

    def _job(self, kind: str, data: np.ndarray, rows: int,
             whole: bool = False, **kw) -> Future:
        """Queue a job over data (n, k) with `rows` result rows. A view a
        caller filled in a lent slot (with room for the parity, where the
        result is the `whole` stripe) is the job's buffer as it stands; any
        other array is copied into a slot, once, unpadded: the snapshot the
        result is built from, whatever the caller does to its array later."""
        data = np.asarray(data, np.uint8)
        n, k = data.shape
        kb = bucket_len(k)
        room = rows if whole else 0
        owner = _lent(data)
        if (owner is not None and data.strides == (kb, 1)
                and owner.size >= (n + room) * kb
                and _address(data) == _address(owner)):
            buf = owner.reshape(-1, kb)
        else:
            buf = self.slot(n, k, room)
            buf[:n, :k] = data
        job = _Job(kind, n, rows, buf, k, kb, whole, **kw)
        self._submit(job)
        return job.future

    def encode(self, n: int, m: int, data: np.ndarray) -> Future:
        """data (n, k) uint8 -> Future[(n+m, k) uint8 full stripe]."""
        if data.shape[0] != n:
            raise ValueError(f"want {n} data rows, got {data.shape}")
        return self._job("encode", data, m, whole=True,
                         plan=rs.get_kernel(n, m).parity_plan)

    def matmul(self, mat: np.ndarray, data: np.ndarray) -> Future:
        """Generic GF(2^8) matmul job: data (rows, k) uint8 ->
        Future[(mat.shape[0], k) uint8]. The raw entry the regenerating-code
        paths ride: PM parity blocks, beta-repair decodes, and any-k
        fallback decodes are all just content-keyed matrices, so they batch
        on the device exactly like RS repairs."""
        mat = np.asarray(mat, np.uint8)
        data = np.asarray(data, np.uint8)
        if data.ndim != 2 or mat.ndim != 2 or data.shape[0] != mat.shape[1]:
            raise ValueError(
                f"matmul shape mismatch: mat {mat.shape} @ data {data.shape}")
        return self._job("matmul", data, mat.shape[0], plan=rs.MatrixPlan(mat))

    def encode_tactic(self, t, data: np.ndarray) -> Future:
        """data (N, k) uint8 -> Future[(total, k) full stripe], local parities
        included for LRC tactics — computed in ONE composed-matrix matmul
        (encoder.lrc_parity_matrix), not a second device pass. Regenerating
        tactics run their PM parity block the same way: one matmul over the
        stripe's sub-unit rows."""
        if t.is_regenerating:
            return self._encode_pm(t, data)
        if not t.L:
            return self.encode(t.N, t.M, data)
        from chubaofs_tpu.codec.encoder import lrc_parity_matrix

        if data.shape[0] != t.N:
            raise ValueError(f"want {t.N} data rows, got {data.shape}")
        return self._job("matmul", data, t.M + t.L, whole=True,
                         plan=rs.MatrixPlan(lrc_parity_matrix(t)))

    def _encode_pm(self, t, data: np.ndarray) -> Future:
        """Product-matrix encode: the stripe's sub-unit rows are the job (the
        parity block applied as one matmul over them), and the stripe is its
        (N + M) * sub_units result rows seen as shards again: a view where a
        sub-unit row is as wide as its bucket, else numpy lays it out anew."""
        from chubaofs_tpu.codec import pm

        if data.shape[0] != t.N:
            raise ValueError(f"want {t.N} data rows, got {data.shape}")
        size = data.shape[1]
        if size % t.sub_units:
            raise ValueError(
                f"shard size {size} not a multiple of sub_units={t.sub_units}")
        kernel = pm.get_kernel(t.total, t.N)
        # (N, size) -> (N * sub_units, size / sub_units): a copy only where
        # data's rows are not back to back (a lent slot's are a bucket apart)
        sub = np.asarray(data, np.uint8).reshape(t.N * t.sub_units, -1)
        f = self._job("matmul", sub, t.M * t.sub_units, whole=True,
                      plan=rs.MatrixPlan(np.asarray(kernel.parity_mat, np.uint8)))
        out = _ChainFuture(f)

        def _finish(fut: Future):
            if fut.cancelled() or out.cancelled():
                # cancelled upstream (drain handshake dropped the job) or
                # downstream (pipeline abort): nothing to deliver
                return
            try:
                if fut.exception():
                    out.set_exception(fut.exception())
                else:
                    out.set_result(fut.result().reshape(t.total, size))
            except InvalidStateError:
                pass  # out.cancel() raced the delivery: outcome discarded

        f.add_done_callback(_finish)
        return out

    def reconstruct_tactic(self, t, shards: np.ndarray, bad_idx: list[int],
                           data_only: bool = False) -> Future:
        """Tactic-aware full-stripe rebuild: RS/LRC global stripes use the
        windowed RS repair matrix; regenerating stripes decode from any N
        intact nodes via the PM generator (the multi-loss fallback — the
        single-loss beta-fetch path lives in the scheduler)."""
        if not t.is_regenerating:
            return self.reconstruct(t.N, t.M, shards, bad_idx, data_only)
        from chubaofs_tpu.codec import pm

        kernel = pm.get_kernel(t.total, t.N)
        bad = sorted(set(int(i) for i in bad_idx))
        want = [i for i in bad if i < t.N] if data_only else bad
        if not want:
            f: Future = Future()
            f.set_result(np.array(shards, copy=True))
            return f
        alive = [i for i in range(t.total) if i not in bad]
        if len(alive) < t.N:
            f = Future()
            f.set_exception(ValueError(
                f"{len(bad)} losses > M={t.M} for regenerating stripe"))
            return f
        srv = alive[: t.N]
        mat = kernel.decode_matrix(srv, want)
        shards = np.asarray(shards, np.uint8)
        size = shards.shape[1]
        job_f = self.matmul(
            mat, shards[np.asarray(srv)].reshape(t.N * t.sub_units, -1))
        out_future: Future = Future()

        def _finish(fut: Future):
            if fut.exception():
                out_future.set_exception(fut.exception())
                return
            fixed = np.array(shards, copy=True)
            fixed[np.asarray(want)] = fut.result().reshape(len(want), size)
            out_future.set_result(fixed)

        job_f.add_done_callback(_finish)
        return out_future

    def reconstruct(
        self, n: int, m: int, shards: np.ndarray, bad_idx: list[int], data_only=False
    ) -> Future:
        """shards (n+m, k) with garbage rows at bad_idx -> Future[repaired copy]."""
        kernel = rs.get_kernel(n, m)
        (plan, present, missing), held = kernel.held_repair(bad_idx, data_only)
        if not missing:
            f: Future = Future()
            f.set_result(np.array(shards, copy=True))
            return f
        shards = np.asarray(shards, np.uint8)
        job_f = self._job("matmul", self.slot_of([shards[i] for i in present]),
                          len(missing), plan=plan, held=held)

        out_future: Future = Future()

        def _finish(f: Future):
            if f.exception():
                out_future.set_exception(f.exception())
                return
            rows = f.result()
            fixed = np.array(shards, copy=True)
            fixed[np.asarray(missing)] = rows
            out_future.set_result(fixed)

        job_f.add_done_callback(_finish)
        return out_future

    def decode_rows(self, n: int, m: int, present: list[int],
                    survivors: np.ndarray, want: list[int]) -> Future:
        """Range-scoped degraded decode: survivors (n, w) uint8 — the chosen
        n survivor shards' bytes over just the window's byte columns, row
        order matching `present` — -> Future[(len(want), w) uint8] holding
        ONLY the wanted shard rows over those columns.

        Never materializes the full stripe: the decode matrix is sliced to
        the wanted rows on the host (RSKernel.window_matrix), so the device
        pass is (len(want), n) @ (n, w) — window-sized both ways. Jobs with
        the identical (present, want) pattern batch on the device exactly
        like repairs (content-keyed matrix signature), and the pattern's
        matrix is computed once (RSKernel.held_window).
        """
        plan, held = rs.get_kernel(n, m).held_window(present, want)
        survivors = np.asarray(survivors, np.uint8)
        if survivors.ndim != 2 or survivors.shape[0] != n:
            raise ValueError(
                f"want ({n}, w) survivors, got {survivors.shape}")
        kb = bucket_len(survivors.shape[1])
        # a row count that has not run here rides the narrowest wider family
        # that has (zero rows padded on, the result cut back) rather than
        # compile its own under a request: a rebuild heals a stripe two rows
        # short into one a row short under the readers, and their next GET
        # must not stall ~2 s a batch count on the chip. Where nothing wider
        # has run (one disk lost: every decode is one row) the count compiles
        # once and runs exact from then on
        rows = plan.mat.shape[0]
        ran = self._decode_rows_run.setdefault((n, kb), set())
        if rows and rows not in ran:
            wider = min((r for r in tuple(ran) if r > rows), default=rows)  # submitters race
            if wider > rows:
                # a padded COPY (the held matrix is never written), found by
                # its content: its operand is as resident as any other
                plan = rs.MatrixPlan(np.concatenate(
                    [plan.mat, np.zeros((wider - rows, n), np.uint8)]))
            else:
                ran.add(rows)
        return self._job("matmul", survivors, rows, plan=plan, held=held)

    def close(self):
        """Idempotent shutdown; jobs enqueued after close() fail fast, jobs
        still queued when the sentinel lands get an exception (never a hang)."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
            started = self._started
        if started:
            self._q.put(None)
            self._thread.join(timeout=5)

    # -- dispatcher --------------------------------------------------------

    def _submit(self, job: _Job):
        job.span = trace.current_span()
        self._ensure_started()
        job.t_submit = time.perf_counter()
        self._q.put(job)

    def _drain(self) -> list[_Job]:
        try:
            first = self._q.get(timeout=0.2)
        except queue.Empty:
            return []
        if first is None:
            raise StopIteration
        batch = [first]
        held = False
        # from the first job taken to the batch closing (the empty poll above
        # is not the dispatcher's work): sweep what is ALREADY queued and
        # launch. Only a caller's hold (max_wait > 0) is ever slept on
        with trace.stage("codec.drain"):
            deadline = time.monotonic() + self.max_wait
            while len(batch) < self.max_batch:
                try:
                    job = self._q.get_nowait()
                except queue.Empty:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        break
                    held = True
                    try:
                        job = self._q.get(timeout=remaining)
                    except queue.Empty:
                        break
                if job is None:
                    self._q.put(None)  # re-post sentinel for the outer loop
                    break
                batch.append(job)
        # how the batch closed, one a batch: "held" = a hold was waited on,
        # "full" = max_batch reached by the sweep alone, "empty" = the sweep
        # ran the queue dry and the batch was launched at once
        close = ("held" if held
                 else "full" if len(batch) >= self.max_batch else "empty")
        registry("codec").counter("batch_close_total", {"close": close}).add()
        return batch

    def _run(self):
        while True:
            try:
                batch = self._drain()
            except StopIteration:
                # fail anything still queued so no caller blocks forever
                while True:
                    try:
                        job = self._q.get_nowait()
                    except queue.Empty:
                        break
                    if job is not None and not job.future.done():
                        job.future.set_exception(RuntimeError("CodecService closed"))
                return
            self._dispatch(batch)
            # the dispatcher holds no job while it waits for the next: a
            # dropped job's slot must not outlive its last view by a poll
            del batch

    def _dispatch(self, batch: list[_Job]) -> None:
        # honor caller-side cancellation (pipeline aborts drop their
        # encode-ahead jobs): a cancelled job is skipped before any
        # device work, and the running-handshake means a later cancel()
        # fails cleanly instead of racing set_result
        batch = [j for j in batch
                 if j.future.set_running_or_notify_cancel()]
        # group by compatible shape signature (kb was bucketed at
        # submission; the drain loop never re-derives shapes). The plan's
        # key is the matrix's CONTENT, made once with the plan: only jobs
        # with the identical matrix share a batch
        groups: dict[tuple, list[_Job]] = {}
        for j in batch:
            groups.setdefault((j.kind, j.plan.key, j.kb), []).append(j)
        for sig, jobs in groups.items():
            try:
                self._run_group(sig, jobs)
            except Exception as e:  # propagate to every waiter
                for j in jobs:
                    if not j.future.done():
                        j.future.set_exception(e)

    def stats_snapshot(self) -> dict:
        """Consistent copy of the legacy counters (no torn reads)."""
        with self._stats_lock:
            return dict(self.stats)

    def _record_batch(self, jobs: int, elapsed_s: float,
                      kind: str = "", plan_hits: int = 0) -> None:
        with self._stats_lock:
            self.stats["batches"] += 1
            self.stats["jobs"] += jobs
            self.stats["max_batch"] = max(self.stats["max_batch"], jobs)
        reg = registry("codec")
        reg.counter("batches_total").add()
        reg.counter("jobs_total").add(jobs)
        # one a job: hit = its matrix, bits, group form and device operand
        # were all found where they are held (RSKernel's patterns, rs's
        # operands); a miss made at least one of them
        reg.counter("plan_total", {"result": "hit"}).add(plan_hits)
        reg.counter("plan_total", {"result": "miss"}).add(jobs - plan_hits)
        if kind:
            # the encode/matmul split: proves repair DECODE really batches
            # on the device (bench_repair and the kill soak read this)
            reg.counter("kind_jobs_total", {"kind": kind}).add(jobs)
        # which lowering did the math: a daemon on the TPU and one that was
        # asked for the CPU must not look the same from /metrics
        lowering = (self._mesh_mm.lowering if self._mesh_mm is not None
                    else rs.lowering())
        reg.counter("lowering_jobs_total", {"lowering": lowering}).add(jobs)
        reg.summary("batch_jobs", buckets=BATCH_BUCKETS).observe(jobs)
        reg.summary("dispatch_seconds").observe(elapsed_s)

    def _run_group(self, sig: tuple, jobs: list[_Job]):
        t0 = time.perf_counter()
        for j in jobs:
            trace.observe_stage("codec.queue_wait", j.t_submit,
                                t0 - j.t_submit, span=j.span)
        # one job is launched where it lies (its slot's input rows, a view);
        # two and more are copied into a batch buffer out of the pool, with
        # room for the next power of two of them (few shapes, their pages
        # mapped by the batches before). Columns past a job's k carry whatever
        # the buffers held: no result column depends on another, and every
        # result is cut back to k
        n, kb = jobs[0].n, jobs[0].kb
        with trace.stage("codec.stack"):
            if len(jobs) == 1:
                stack = jobs[0].buf[None, :n]
            else:
                room = 1 << (len(jobs) - 1).bit_length()
                stack = self._pool.take(
                    "batch", (room, n, kb), self.max_batch)[: len(jobs)]
                for i, j in enumerate(jobs):
                    stack[i, :, : j.k] = j.buf[:n, : j.k]
        t_mm = time.perf_counter()
        # both paths go through the host-boundary grouped entry: batches of
        # stripes are viewed (free numpy reshape) as MXU-row-filling groups
        # before they ever reach the device (rs.gf_matmul_hostbatch, which
        # records the hostbatch.* stages and launches with the plan's
        # RESIDENT operand) — or, with a mesh, fan out dp/sp-sharded across
        # every device from the plan's host bits, as before
        plan = jobs[0].plan
        mesh = self._mesh_mm
        ready = plan.ready(len(jobs)) if mesh is None else plan.expanded
        if sig[0] != "encode":
            # the matrix's bits, where this launch has to make its operand
            # from them: a dozen small numpy calls, each a chance to hand the
            # interpreter lock over, so they have a name of their own;
            # nothing on a hit
            with trace.stage("codec.expand"):
                if not ready:
                    plan.bits()
        if mesh is None:
            out = rs.gf_matmul_hostbatch(plan, stack)
        else:
            out = mesh(plan.bits(), stack)
        # a stripe's parity rows go into the tail rows of its own slot: the
        # future gets a view of the slot, and nothing is concatenated. Rows
        # asked for alone (a decode's) are handed out where they were fetched
        with trace.stage("codec.concat"):
            for i, j in enumerate(jobs):
                if j.whole:
                    j.buf[n: n + j.rows, : j.k] = out[i, : j.rows, : j.k]
        t_done = time.perf_counter()
        with trace.stage("codec.deliver"):  # bookkeeping, then the results
            self._record_batch(len(jobs), t_done - t0, kind=str(sig[0]),
                               plan_hits=sum(ready and j.held for j in jobs))
            for j in jobs:
                if j.span is not None:
                    # the BATCH's wall intervals, attributed to every rider:
                    # the job was being stacked / in the matmul (expand,
                    # group, H2D, kernel, D2H, concat: host wall, not device
                    # time) during exactly these windows (shared across the
                    # batch — sums can exceed the dispatcher's seconds,
                    # wall-clock union cannot)
                    j.span.add_stage("codec.stack", start=t0, dur=t_mm - t0)
                    j.span.add_stage("codec.matmul", start=t_mm,
                                     dur=t_done - t_mm)
            for i, j in enumerate(jobs):
                j.future.set_result(j.buf[: n + j.rows, : j.k] if j.whole
                                    else out[i, : j.rows, : j.k])


_default: CodecService | None = None
_default_lock = SanitizedLock(name="codec.default")


def default_service() -> CodecService:
    global _default
    with _default_lock:
        if _default is None:
            _default = CodecService()
        return _default
