"""CodecService — the batching device sidecar for erasure-coding math.

Reference analog: the access layer encodes each blob inline on the CPU
(stream_put.go:143 `encoder.Encode`) and blobnode workers reconstruct per-task
(work_shard_recover.go:422). On TPU, per-blob dispatch would waste the chip:
each call pays host->device latency, and small stripes underfill the MXU. This
service is the TPU-native replacement:

  * callers submit encode/repair jobs (numpy matrices) and get futures back;
  * a dispatcher thread drains the queue, groups jobs by (layout, k-bucket),
    pads each shard length up to the bucket, stacks them into one (B, n, k)
    device batch, runs ONE fused-kernel call, then scatters results back;
  * shard lengths are bucketed to powers of two (>= 16 KiB) so the jit cache
    stays small and the MXU sees few distinct shapes;
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

import queue
import threading
import time
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
    n: int
    m: int
    data: np.ndarray  # (rows, kb) uint8 — PRE-PADDED to the shape bucket
    k: int  # true shard length (result is sliced back to it)
    kb: int  # bucket_len(k), computed at submission
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
    # result rows the caller asked for, where decode_rows padded the matrix
    # past them to ride a resident program; None: all of them
    rows: int | None = None


def _pad_to_bucket(data: np.ndarray, k: int, kb: int) -> np.ndarray:
    """Pad (rows, k) up to (rows, kb) on the SUBMITTING thread — the drain
    loop then only stacks, and padding cost parallelizes across callers
    instead of serializing on the dispatcher."""
    if k == kb:
        return np.ascontiguousarray(data, np.uint8)
    out = np.zeros((data.shape[0], kb), np.uint8)
    out[:, :k] = data
    return out


class CodecService:
    """Queue -> padded device batches -> futures. Thread-safe, one device stream."""

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

    def _ensure_started(self):
        with self._lock:
            if self._closed:
                raise RuntimeError("CodecService is closed")
            if not self._started:
                self._thread.start()
                self._started = True

    # -- public API --------------------------------------------------------

    def encode(self, n: int, m: int, data: np.ndarray) -> Future:
        """data (n, k) uint8 -> Future[(n+m, k) uint8 full stripe]."""
        if data.shape[0] != n:
            raise ValueError(f"want {n} data rows, got {data.shape}")
        k = data.shape[1]
        kb = bucket_len(k)
        job = _Job("encode", n, m, _pad_to_bucket(data, k, kb), k, kb,
                   plan=rs.get_kernel(n, m).parity_plan)
        self._submit(job)
        return job.future

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
        k = data.shape[1]
        kb = bucket_len(k)
        job = _Job("matmul", data.shape[0], mat.shape[0],
                   _pad_to_bucket(data, k, kb), k, kb, plan=rs.MatrixPlan(mat))
        self._submit(job)
        return job.future

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
        # snapshot ONCE (explicit copy) and build the result from the same
        # snapshot the job computed parity from — caller-side dtype changes or
        # post-submit mutation must never yield a stripe whose data rows don't
        # match its parity
        data = np.array(data, np.uint8, order="C")
        mat = lrc_parity_matrix(t)
        k = data.shape[1]
        kb = bucket_len(k)
        job = _Job("matmul", t.N, t.M + t.L, _pad_to_bucket(data, k, kb),
                   k, kb, plan=rs.MatrixPlan(mat))
        self._submit(job)
        out = _ChainFuture(job.future)

        def _finish(f: Future):
            if f.cancelled() or out.cancelled():
                # cancelled upstream (drain handshake dropped the job) or
                # downstream (pipeline abort): nothing to deliver
                return
            try:
                if f.exception():
                    out.set_exception(f.exception())
                else:
                    out.set_result(
                        np.concatenate([data, f.result()], axis=0))
            except InvalidStateError:
                pass  # out.cancel() raced the delivery: outcome discarded

        job.future.add_done_callback(_finish)
        return out

    def _encode_pm(self, t, data: np.ndarray) -> Future:
        """Product-matrix encode: shard rows reshaped (free) to sub-unit
        rows, parity block applied as one matmul, parity rows reshaped back
        to shards. Same snapshot discipline as the LRC path."""
        from chubaofs_tpu.codec import pm

        if data.shape[0] != t.N:
            raise ValueError(f"want {t.N} data rows, got {data.shape}")
        size = data.shape[1]
        if size % t.sub_units:
            raise ValueError(
                f"shard size {size} not a multiple of sub_units={t.sub_units}")
        data = np.array(data, np.uint8, order="C")
        kernel = pm.get_kernel(t.total, t.N)
        f = self.matmul(kernel.parity_mat,
                        data.reshape(t.N * t.sub_units, -1))
        out = _ChainFuture(f)

        def _finish(fut: Future):
            if fut.cancelled() or out.cancelled():
                return
            try:
                if fut.exception():
                    out.set_exception(fut.exception())
                else:
                    parity = fut.result().reshape(t.M, size)
                    out.set_result(np.concatenate([data, parity], axis=0))
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
        k = shards.shape[1]
        kb = bucket_len(k)
        survivors = _pad_to_bucket(
            np.asarray(shards, np.uint8)[np.asarray(present)], k, kb)
        job = _Job("matmul", n, m, survivors, k, kb, plan=plan, held=held)
        self._submit(job)

        out_future: Future = Future()

        def _finish(f: Future):
            if f.exception():
                out_future.set_exception(f.exception())
                return
            rows = f.result()
            fixed = np.array(shards, copy=True)
            fixed[np.asarray(missing)] = rows
            out_future.set_result(fixed)

        job.future.add_done_callback(_finish)
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
        k = survivors.shape[1]
        kb = bucket_len(k)
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
            wider = min((r for r in ran if r > rows), default=rows)
            if wider > rows:
                # a padded COPY (the held matrix is never written), found by
                # its content: its operand is as resident as any other
                plan = rs.MatrixPlan(np.concatenate(
                    [plan.mat, np.zeros((wider - rows, n), np.uint8)]))
            else:
                ran.add(rows)
        job = _Job("matmul", n, m, _pad_to_bucket(survivors, k, kb),
                   k, kb, plan=plan, held=held, rows=rows)
        self._submit(job)
        return job.future

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
            if not batch:
                continue
            # honor caller-side cancellation (pipeline aborts drop their
            # encode-ahead jobs): a cancelled job is skipped before any
            # device work, and the running-handshake means a later cancel()
            # fails cleanly instead of racing set_result
            batch = [j for j in batch
                     if j.future.set_running_or_notify_cancel()]
            if not batch:
                continue
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
        # jobs arrive pre-padded to the bucket: stacking is the whole job
        # here, and one job is launched where it lies (a view, no copy)
        with trace.stage("codec.stack"):
            stack = (jobs[0].data[None] if len(jobs) == 1
                     else np.stack([j.data for j in jobs]))
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

        def mm():
            if mesh is None:
                return rs.gf_matmul_hostbatch(plan, stack)
            return mesh(plan.bits(), stack)

        if sig[0] == "encode":
            parity = mm()
            with trace.stage("codec.concat"):
                out = np.concatenate([stack, parity], axis=1)  # (B, n+m, kb)
        else:
            # the matrix's bits, where this launch has to make its operand
            # from them: a dozen small numpy calls, each a chance to hand the
            # interpreter lock over, so they have a name of their own;
            # nothing on a hit
            with trace.stage("codec.expand"):
                if not ready:
                    plan.bits()
            out = mm()
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
                j.future.set_result(out[i, : j.rows, : j.k])


_default: CodecService | None = None
_default_lock = SanitizedLock(name="codec.default")


def default_service() -> CodecService:
    global _default
    with _default_lock:
        if _default is None:
            _default = CodecService()
        return _default
