"""BlobNode — per-host chunk storage engine.

Reference counterpart: blobstore/blobnode (disks -> chunks -> shards; append-only
chunk datafiles with per-shard headers and crc32block framing,
core/storage/datafile.go:356,416; RocksDB shard metadb; punch-hole GC,
core/blobfile.go:83). Same on-disk contracts — append-only data files,
block-CRC framing, a persistent shard index, hole punching on delete — with
the shard index in the native libcfskv engine (utils/kvstore), exactly the
role RocksDB plays under the reference blobnode.

Layout on disk:
    <root>/superblock.json                 disk identity + chunk registry
    <root>/chunks/<chunk_id>.data          append-only shard records (extent 0)
    <root>/chunks/<chunk_id>.x<k>.data     extent k of the same datafile (_Extents)
    <root>/metadb/                         per-disk shard index (libcfskv — the
                                           native KV engine standing in for the
                                           reference's RocksDB metadb,
                                           blobnode/db/metadb.go); keys
                                           s/<chunk_id>/<bid> -> ShardMeta json.
                                           Legacy <chunk_id>.idx JSON-line WALs
                                           migrate into the metadb on open.

Shard record in a chunk datafile:
    [32B header: magic, bid, vuid, payload_len, header_crc]
    [crc32block-framed payload]

A chunk holds its datafile as a file DESCRIPTOR and every access is positional
(utils/crc32block `pwrite` / `pread`: the framing and the I/O of a shard are
one call, one release of the interpreter lock). No buffered file object ever
sits on a datafile: there is no file position to share and nothing to flush.
Durability is what it always was here: when put_shard returns, the record is
in the OS (written, not fsynced) and its meta in the metadb; only compaction
syncs, before its commit.

Deletes are two phases, each a chunk's BATCH of bids in one take of the chunk
lock and one metadb batch (blob_deleter.go: markDelete on every unit, then
delete): `mark_delete_batch` makes the bids unreadable and durable as such,
`delete_batch` punches their records (never before the mark is in the index)
and leaves tombstones. Compaction copies OUTSIDE the chunk lock and takes it
only to catch up and swap (Chunk.compact).

What a delete gives back to the filesystem, and when: the punch, at once,
where the filesystem takes it (`cfs_blobnode_punched_bytes`); where it refuses
(`cfs_blobnode_punch_failed`: a 9p or NFS root), the record's bytes stay held
until the EXTENT they lie in has no live record left and is unlinked (no copy;
_Extents), or until the chunk's compaction. `Chunk.used` is the datafile's
length, as it always was; `Chunk.held` is what the filesystem still holds of
it, and `cfs_blobnode_released_bytes` counts every byte that really went back.
"""

from __future__ import annotations

import ctypes
import json
import os
import re
import struct
import threading
import time
import weakref
import zlib
from dataclasses import dataclass

from chubaofs_tpu import chaos
from chubaofs_tpu.blobstore import trace
from chubaofs_tpu.blobstore.clustermgr import DISK_BROKEN, DISK_NORMAL
from chubaofs_tpu.utils import crc32block, exporter
from chubaofs_tpu.utils.exporter import registry
from chubaofs_tpu.utils.locks import SanitizedLock
from chubaofs_tpu.utils.kvstore import open_kv

MAGIC = 0x73686472  # "shdr"
_HEADER = struct.Struct("<IQQQI")  # magic, bid, vuid, payload_len, crc-of-header
HEADER_LEN = _HEADER.size

# shard index states (metadb values)
STATUS_NORMAL = 1
STATUS_MARK_DELETE = 2
STATUS_DELETED = 3


_libc = None


def _punch_hole(fd: int, offset: int, length: int) -> bool:
    """Release a byte range back to the filesystem (core/blobfile.go:83 analog).

    FALLOC_FL_PUNCH_HOLE | FALLOC_FL_KEEP_SIZE; best-effort — filesystems
    without hole support (a 9p root answers EOPNOTSUPP) just keep the bytes
    until compaction. Says whether the filesystem took it."""
    global _libc
    try:
        if _libc is None:
            _libc = ctypes.CDLL(None, use_errno=True)
            _libc.fallocate.argtypes = (ctypes.c_int, ctypes.c_int,
                                        ctypes.c_int64, ctypes.c_int64)
        return _libc.fallocate(fd, 0x03, offset, length) == 0
    except Exception:
        return False


class BlobNodeError(Exception):
    pass


class NoSuchShard(BlobNodeError):
    pass


class ShardDeleted(NoSuchShard):
    """The index holds the bid MARK_DELETE or as a tombstone: deleted here,
    not lost. A reader answers not-found on the first one it meets and
    reports nothing to the repair plane."""


class ChunkFull(BlobNodeError):
    pass


class _ChunkClosed(BlobNodeError):
    """close() or destroy() reached a chunk whose compaction was copying."""


class DiskBroken(BlobNodeError):
    """The cluster manager holds the chunk's disk other than NORMAL: the node
    answers no shard I/O for it (upstream's blobnode refuses a broken disk's
    chunk I/O the same way)."""


def classify_io_error(e: BaseException) -> str:
    """Bucket a shard-IO failure for {reason}-labeled metrics: 'missing'
    (routine absence — the shard was never written or already lost),
    'disk_broken' (the node refused: its disk is held BROKEN or DROPPED),
    'timeout' (a silent hang that hit a deadline), 'io' (infrastructure:
    sockets, disks, injected faults), or 'error' (everything else — the
    bucket that should be a bug). The split is what makes a wedged node and
    a real defect distinguishable on a dashboard."""
    from concurrent.futures import TimeoutError as _FutTimeout

    from chubaofs_tpu.chaos.failpoints import Dropped, FailpointError

    if isinstance(e, NoSuchShard):
        return "missing"
    if isinstance(e, DiskBroken):
        return "disk_broken"
    if isinstance(e, (TimeoutError, _FutTimeout)):
        return "timeout"
    if isinstance(e, (BlobNodeError, OSError, ConnectionError,
                      FailpointError, Dropped)):
        return "io"
    return "error"


@dataclass
class ShardMeta:
    bid: int
    vuid: int
    offset: int  # offset of the record header in the datafile
    size: int  # payload length (unframed)
    status: int = STATUS_NORMAL


def _record_len(meta: ShardMeta) -> int:
    return HEADER_LEN + crc32block.encoded_len(meta.size)


_DATA_SUFFIX = re.compile(r"(?:\.g(\d+))?(?:\.x(\d+))?\.data")


def _parse_data_name(stem: str, fname: str) -> tuple[int, int] | None:
    """(generation, extent) of a chunk's datafile name, None for any other
    file: 'vuid-2560.data' is NOT a file of chunk 'vuid-256'."""
    if not fname.startswith(stem):
        return None
    m = _DATA_SUFFIX.fullmatch(fname[len(stem):])
    return None if m is None else (int(m.group(1) or 0), int(m.group(2) or 0))


class _Extents:
    """One generation of a chunk's datafile, as extent files.

    The datafile's offsets are cut every `span` bytes: extent k holds
    [k x span, (k+1) x span) in a file of its own, `<chunk>[.g<G>][.x<k>].data`
    (extent 0 carries no `.x`, so a chunk smaller than one span is the one
    file it always was), and no record straddles two: the one that would is
    placed at the start of the next, and the rest of its extent is never
    written. A datafile written before there were extents is a long extent 0
    (`long0`), appended to by extents past its end.

    What the cut buys: an extent whose records are all dead is UNLINKED. That
    gives its bytes back on any filesystem and copies nothing, where a punch
    needs the filesystem's consent and a compaction copies every live record
    of the chunk. A retention policy expires oldest first, and a chunk is
    appended in time order, so its dead records fill whole extents."""

    def __init__(self, base: str, gen: int, span: int):
        self.base, self.gen, self.span = base, gen, span
        self.fds: dict[int, int] = {}  # extent -> descriptor
        self.long0 = 0
        self.closed = False

    def path(self, k: int = 0) -> str:
        return (self.base + (f".g{self.gen}" if self.gen else "")
                + (f".x{k}" if k else "") + ".data")

    def _files(self) -> list[tuple[int, str]]:
        d, stem = os.path.split(self.base)
        d = d or "."
        got = ((_parse_data_name(stem, f), os.path.join(d, f)) for f in os.listdir(d))
        return [(name[1], full) for name, full in got if name is not None and name[0] == self.gen]

    def open_present(self) -> None:
        for k, full in self._files():
            self.fds[k] = os.open(full, os.O_RDWR)
        if 0 in self.fds and os.fstat(self.fds[0]).st_size > self.span:
            self.long0 = os.fstat(self.fds[0]).st_size

    def unlink_present(self) -> None:
        """Files of this generation that nobody holds open (a compaction that
        failed in this process left them; one that crashed is swept on open)."""
        for _, full in self._files():
            os.unlink(full)

    def k(self, at: int) -> int:
        return 0 if at < self.long0 else at // self.span

    def place(self, at: int, length: int) -> int:
        """Where a record of `length` bytes goes when the datafile ends at
        `at`: there, or at the start of the next extent if it would straddle."""
        if length > self.span:
            raise BlobNodeError(f"a record of {length} bytes is larger than an extent ({self.span})")
        room = self.span - at % self.span
        return at if length <= room else at + room

    def fd(self, at: int, create: bool = False) -> tuple[int, int]:
        """(descriptor, offset in its file) of datafile offset `at`."""
        k = self.k(at)
        fd = self.fds.get(k)
        if fd is None and self.closed:
            fd = -1  # the OS refuses it (EBADF), as any closed descriptor
        if fd is None:
            if not create:
                raise BlobNodeError(f"{self.path(k)}: no such extent")
            fd = self.fds[k] = os.open(self.path(k), os.O_RDWR | os.O_CREAT, 0o644)
        return fd, at - (0 if k == 0 else k * self.span)

    def end(self) -> int:
        if not self.fds:
            return 0
        k = max(self.fds)
        return (k * self.span if k else 0) + os.fstat(self.fds[k]).st_size

    def held(self, k: int) -> int:
        """Bytes the filesystem holds of extent k (a punched range holds none)."""
        st = os.fstat(self.fds[k])
        return min(st.st_size, st.st_blocks * 512)

    def adopt(self, other: "_Extents") -> None:
        """Descriptors of our own (a compaction's copy reads through them:
        close(), destroy() and a dropped extent cannot pull them) for every
        extent `other` has and we have not."""
        self.long0 = other.long0
        for k in other.fds.keys() - self.fds.keys():
            self.fds[k] = os.dup(other.fds[k])

    def sync(self) -> None:
        for fd in self.fds.values():
            os.fsync(fd)
        # the new files' DIRECTORY ENTRIES must be durable before the gen bump
        # commits, or a crash could leave a committed gen with no file
        dfd = os.open(os.path.dirname(self.base) or ".", os.O_RDONLY)
        try:
            os.fsync(dfd)
        finally:
            os.close(dfd)

    def drop(self, k: int) -> None:
        fd = self.fds.pop(k)
        if fd >= 0:
            os.close(fd)
        os.unlink(self.path(k))

    def close(self) -> None:
        """Idempotent. A descriptor's number is reused by the next open in the
        process: each is closed once and a later call gets -1, never it."""
        self.closed = True
        for k, fd in self.fds.items():
            if fd >= 0:
                os.close(fd)
                self.fds[k] = -1

    def unlink_all(self) -> None:
        self.closed = True
        for k in list(self.fds):
            try:
                self.drop(k)
            except OSError:
                pass


class Chunk:
    """One append-only chunk datafile + its shard index.

    Compaction is generational (core/storage compaction analog): gen G lives
    in `<chunk>.data` (G=0) or `<chunk>.g<G>.data`, cut into extent files
    (_Extents); a compaction writes gen G+1 fully (outside the chunk lock; see
    `compact`), then commits the gen bump AND every re-offset shard meta in
    ONE atomic metadb batch. A crash before the batch leaves gen G valid (the
    orphan G+1 files are swept on open); after it, gen G+1 is valid and stale
    files are swept on open.

    `_lock` guards the offset reservation (`_size`), `shards`, the metadb
    put, the descriptors and compaction's swap of them. Reads hold it through
    their one positional call, so `compact` / `delete` / `destroy` / `close`
    never pull a file (or a descriptor's number) from under a reader.

    Three sizes, all in bytes of the datafile: `used` its length (what was
    ever appended to this generation, the skipped ends of extents included),
    `holes` the part of it no live record owns, `held` what the filesystem
    still holds of it (`used` less what a punch, a dropped extent or a skip
    gave back). `held - live` is dead and still paid for: what a filesystem
    that refuses the punch keeps until the extent dies or the chunk compacts.
    """

    # the datafile's cut (see _Extents); a chunk of at most one span is one file
    EXTENT_SIZE = 64 << 20

    def __init__(self, path: str, chunk_id: str, max_size: int, metadb):
        self.chunk_id = chunk_id
        self.max_size = max_size
        self._base_path = path
        self._idx_path = path + ".idx"  # legacy json-line WAL (migrated)
        self._db = metadb
        self._lock = SanitizedLock(name="blobnode.chunk")
        self._compact_lock = threading.Lock()  # one compaction a chunk at a time
        self.shards: dict[int, ShardMeta] = {}
        self.gen = int(self._db.get(self._gen_key()) or 0)
        self._x = _Extents(path, self.gen, self.EXTENT_SIZE)
        self._data_path = self._x.path()
        self.tombstones: set[int] = set()  # deleted bids (metadb tombstones)
        self._check_committed_gen()
        self._sweep_stale_gens()
        self._load()
        self._x.open_present()
        if not self._x.fds:
            self._x.fd(0, create=True)
        self._closed = False
        self._size = self._x.end()
        self._account_locked()

    def _account_locked(self) -> None:
        """From the index and the files: `holes` (everything in the datafile
        that is not a live record: the compaction trigger survives restarts),
        each extent's live bytes, and what the filesystem holds of each. An
        extent with no live record (but the last) is dropped here too."""
        self._live_in: dict[int, int] = {}  # extent -> bytes of records in `shards`
        for m in self.shards.values():
            k = self._x.k(m.offset)
            self._live_in[k] = self._live_in.get(k, 0) + _record_len(m)
        self.holes = max(0, self._size - sum(self._live_in.values()))
        self._kept: dict[int, int] = {}  # extent -> dead bytes the filesystem still holds
        held = 0
        for k in sorted(self._x.fds):
            if not self._live_in.get(k) and k != max(self._x.fds):
                self._x.drop(k)
                continue
            here = self._x.held(k)
            held += here
            self._kept[k] = max(0, here - self._live_in.get(k, 0))
        self.released = self._size - held  # bytes of the datafile the filesystem does not hold

    @property
    def _fd(self) -> int:
        """-1 once closed (tests and tools look; the I/O goes through _x)."""
        return -1 if self._closed else self._x.fds[max(self._x.fds)]

    def _check_committed_gen(self):
        """Never sweep while the committed generation has no datafile and
        another has: deleting the survivors would turn a recoverable
        inconsistency into silent data loss. (compact() fsyncs the directory
        before the commit, so this only fires on external damage — fail
        loudly.)"""
        d, stem = os.path.split(self._base_path)
        gens = {name[0] for f in os.listdir(d or ".")
                if (name := _parse_data_name(stem, f)) is not None}
        if gens and self.gen not in gens:
            raise BlobNodeError(
                f"chunk {self.chunk_id}: committed gen {self.gen} datafile "
                f"missing but generations {sorted(gens)} exist — refusing to sweep")

    def _gen_key(self) -> bytes:
        return f"g/{self.chunk_id}".encode()

    def _gen_path(self, gen: int) -> str:
        return _Extents(self._base_path, gen, self.EXTENT_SIZE).path()

    def _sweep_stale_gens(self):
        """Drop datafiles of any generation other than the committed one."""
        d, stem = os.path.split(self._base_path)
        for fname in os.listdir(d or "."):
            name = _parse_data_name(stem, fname)
            if name is not None and name[0] != self.gen:
                os.unlink(os.path.join(d, fname))

    def _key(self, bid: int) -> bytes:
        # fixed-width decimal keeps the metadb's byte order == bid order
        return f"s/{self.chunk_id}/{bid:020d}".encode()

    def _load(self):
        if os.path.exists(self._idx_path):  # migrate a legacy index WAL
            with open(self._idx_path) as f:
                for line in f:
                    if not line.strip():
                        continue
                    # DELETED entries become tombstones too: delete intent
                    # must survive the migration or the inspector could
                    # resurrect a partially-deleted blob
                    meta = ShardMeta(**json.loads(line))
                    self._db.put(self._key(meta.bid),
                                 json.dumps(meta.__dict__).encode())
            os.replace(self._idx_path, self._idx_path + ".migrated")
        for _, v in self._db.scan(prefix=f"s/{self.chunk_id}/".encode()):
            meta = ShardMeta(**json.loads(v))
            if meta.status == STATUS_DELETED:
                self.tombstones.add(meta.bid)  # deleted, not lost
            else:
                self.shards[meta.bid] = meta

    def _log_idx(self, meta: ShardMeta):
        # STATUS_DELETED stays in the metadb as a TOMBSTONE: the volume
        # inspector must be able to tell "deleted here" from "lost here", or a
        # partially-applied blob delete would be resurrected as a repair
        self._db.put(*self._encode(meta))

    @property
    def used(self) -> int:
        return self._size

    @property
    def held(self) -> int:
        return 0 if self._closed else self._size - self.released

    @property
    def live(self) -> int:
        return self._size - self.holes

    def put(self, bid: int, vuid: int, payload: bytes) -> ShardMeta:
        head = _HEADER.pack(MAGIC, bid, vuid, len(payload), 0)[:-4]
        head += struct.pack("<I", zlib.crc32(head))
        with trace.mark("chunk.lock_wait"):
            self._lock.acquire()
        try:
            return self._put_locked(bid, vuid, head, payload)
        finally:
            self._lock.release()

    def _put_locked(self, bid: int, vuid: int, head: bytes,
                    payload: bytes) -> ShardMeta:
        length = HEADER_LEN + crc32block.encoded_len(len(payload))
        offset = self._x.place(self._size, length)
        if offset + length > self.max_size:
            raise ChunkFull(self.chunk_id)
        old = self.shards.get(bid)
        fd, local = self._x.fd(offset, create=True)
        # header + framed payload, checksummed and written in one call; on
        # return the record is in the OS, as after write + flush
        with trace.mark("chunk.write"):
            crc32block.pwrite(fd, local, payload, prefix=head)
        skipped = offset - self._size  # the end of an extent: nobody's, never written
        self.holes += skipped
        self.released += skipped
        self._size = offset + length
        k = self._x.k(offset)
        self._live_in[k] = self._live_in.get(k, 0) + length
        meta = ShardMeta(bid=bid, vuid=vuid, offset=offset, size=len(payload))
        self.shards[bid] = meta
        self.tombstones.discard(bid)  # re-put over a tombstone revives it
        with trace.mark("chunk.meta"):
            self._log_idx(meta)
        if old is not None:
            # re-put (e.g. repeated repair): release the superseded record
            self._punch_locked(old)
        return meta

    def _punch_locked(self, meta: ShardMeta) -> None:
        """The record becomes a hole: cfs_blobnode_hole_bytes counts it
        (deleted, superseded or lost), and its bytes are given back."""
        length = _record_len(meta)
        registry("blobnode").counter("hole_bytes").add(length)
        self.holes += length
        self._release_locked(meta.offset, length, counted=True)

    def _release_locked(self, offset: int, length: int, counted: bool) -> None:
        """Give a dead record's bytes back to the filesystem. Where it takes
        the punch they go at once (cfs_blobnode_punched_bytes); where it
        refuses (cfs_blobnode_punch_failed) they stay held until the last
        record of their extent is dead and the extent is unlinked, or the
        chunk compacts. cfs_blobnode_released_bytes counts what went back, by
        either way, when it went. ``counted`` False: a compaction's garbage,
        whose record these counters met when it died; the compaction counts
        what it wrote dead and what it gave back itself."""
        k = self._x.k(offset)
        if k not in self._x.fds:
            return  # the extent is gone (damage from outside): nothing is held
        fd, local = self._x.fd(offset)
        punched = _punch_hole(fd, local, length)
        back = length if punched else 0
        if not punched:
            self._kept[k] = self._kept.get(k, 0) + length
        self._live_in[k] = self._live_in.get(k, 0) - length
        dropped = self._live_in[k] <= 0 and k != max(self._x.fds)
        if dropped:
            self._x.drop(k)
            back += self._kept.pop(k, 0)
        self.released += back
        if counted:
            reg = registry("blobnode")
            if punched:
                reg.counter("punched_bytes").add(length)
            else:
                reg.counter("punch_failed").add()
            if dropped:
                reg.counter("extents_dropped").add()
            reg.counter("released_bytes").add(back)

    def get(self, bid: int, offset: int = 0, size: int | None = None) -> bytes:
        with self._lock:
            meta = self.shards.get(bid)
            if meta is None or meta.status != STATUS_NORMAL:
                if meta is not None or bid in self.tombstones:
                    raise ShardDeleted(f"chunk {self.chunk_id} bid {bid}")
                raise NoSuchShard(f"chunk {self.chunk_id} bid {bid}")
            if size is None:
                size = meta.size - offset
            if offset < 0 or size < 0 or offset + size > meta.size:
                raise BlobNodeError(f"range [{offset}, {offset+size}) outside shard of {meta.size}")
            # only the blocks that cover the range are read; every one of
            # them is verified in the same call
            fstart, fend = crc32block.block_range(offset, size)
            fend = min(fend, crc32block.encoded_len(meta.size))
            fd, local = self._x.fd(meta.offset)
            with trace.mark("chunk.verify"):
                blocks = crc32block.pread(fd, local + HEADER_LEN + fstart, fend - fstart)
        inner = offset - (fstart // (crc32block.BLOCK_SIZE + 4)) * crc32block.BLOCK_SIZE
        return blocks[inner : inner + size]

    # -- delete: two phases, a batch of bids a lock take ------------------------

    def _encode(self, meta: ShardMeta, status: int | None = None) -> tuple[bytes, bytes]:
        """The metadb entry of ``meta`` (under ``status``, where given)."""
        entry = meta.__dict__ if status is None else {**meta.__dict__, "status": status}
        return self._key(meta.bid), json.dumps(entry).encode()

    def _held_locked(self, bids) -> list[ShardMeta]:
        return [m for b in bids if (m := self.shards.get(b)) is not None]

    def _mark_locked(self, metas: list[ShardMeta]) -> None:
        """ONE metadb batch, written before the memory says so."""
        if metas:
            self._db.write_batch(
                puts=[self._encode(m, STATUS_MARK_DELETE) for m in metas])
            for m in metas:
                m.status = STATUS_MARK_DELETE

    def mark_delete_batch(self, bids) -> int:
        """Phase one: the bids stop being readable, durably. Bids this chunk
        does not hold are passed by; returns how many it marked.

        Stage `chunk.delete` with the chunk lock taken inside it; the wait for
        the lock is `chunk.delete_wait` (observed): the shard writers hold it
        meanwhile, and what is left of the stage is how long they wait for us."""
        with trace.stage("chunk.delete"):
            t0 = time.perf_counter()
            with self._lock:
                trace.observe_stage("chunk.delete_wait", t0, time.perf_counter() - t0)
                metas = self._held_locked(bids)
                self._mark_locked(metas)
                return len(metas)

    def delete_batch(self, bids) -> int:
        """Phase two: punch the records out and leave tombstones; returns how
        many went. Nothing is released before its mark-delete is in the
        index: a bid that reaches here unmarked (a direct delete, the
        inspector finishing a partial one) is marked in the same take first,
        so a crash between the punch and the tombstone reopens a shard that
        is not served and is punched again on replay."""
        with trace.stage("chunk.delete"):
            t0 = time.perf_counter()
            with self._lock:
                trace.observe_stage("chunk.delete_wait", t0, time.perf_counter() - t0)
                metas = self._held_locked(bids)
                if not metas:
                    return 0
                self._mark_locked([m for m in metas if m.status != STATUS_MARK_DELETE])
                chaos.failpoint("blobnode.delete_punch")
                for m in metas:
                    self._punch_locked(m)
                # STATUS_DELETED stays in the metadb as a TOMBSTONE (_log_idx)
                self._db.write_batch(
                    puts=[self._encode(m, STATUS_DELETED) for m in metas])
                for m in metas:
                    m.status = STATUS_DELETED
                    self.tombstones.add(m.bid)
                    del self.shards[m.bid]
                return len(metas)

    def mark_delete(self, bid: int):
        if not self.mark_delete_batch((bid,)):
            raise NoSuchShard(f"chunk {self.chunk_id} bid {bid}")

    def delete(self, bid: int):
        """Punch-hole delete: release the record's bytes, drop the index entry."""
        if not self.delete_batch((bid,)):
            raise NoSuchShard(f"chunk {self.chunk_id} bid {bid}")

    # -- compaction: copy outside the lock, catch up and swap under it ----------

    # what one take of the chunk lock may copy: records appended while the
    # copy ran are caught up outside it, round by round, until the tail is
    # at most this (or CATCH_UP_ROUNDS have run: a chunk written faster than
    # it is copied must still end)
    SWAP_TAIL_MAX = 4 << 20
    CATCH_UP_ROUNDS = 6

    @staticmethod
    def _copy_records(src: _Extents, dst: _Extents, metas, at: int) -> tuple[list, int]:
        """Copy whole records (header + framed payload) to ``dst`` from
        offset ``at`` on; -> ([(source meta, new offset)], the new end)."""
        placed = []
        for meta in metas:
            length = _record_len(meta)
            fd, local = src.fd(meta.offset)
            record = crc32block.pread_exact(fd, length, local)
            at = dst.place(at, length)
            fd, local = dst.fd(at, create=True)
            crc32block.pwrite_all(fd, record, local)
            placed.append((meta, at))
            at += length
        return placed, at

    def compact(self) -> int:
        """Rewrite the datafile keeping only live records; returns bytes
        reclaimed. Crash-safe via the generational commit described on the
        class docstring.

        The chunk lock is NOT held while the live records are copied and the
        new files are synced (stage `chunk.compact`): PUTs, GETs and deletes of
        the chunk go on against generation G. The lock is taken three times:
        to snapshot the index (descriptors of its own for the copy: close(),
        destroy() and a dropped extent cannot pull them), for each catch-up
        round's listing, and last for the swap (stage `chunk.compact_swap`):
        the records appended since the last listing are copied (at most
        SWAP_TAIL_MAX unless the rounds ran out) and synced, every snapshot
        record is looked up again (deleted or superseded meanwhile: its copy
        is garbage in G+1, punched there; marked meanwhile: the mark goes with
        it), then the gen bump and every re-offset meta commit in ONE metadb
        batch and the descriptors are swapped. One compaction a chunk at a
        time."""
        with self._compact_lock, trace.stage("chunk.compact"):
            src = _Extents(self._base_path, self.gen, self.EXTENT_SIZE)
            with self._lock:
                if self._closed:
                    return 0
                new = _Extents(self._base_path, self.gen + 1, self.EXTENT_SIZE)
                todo = self._listed_from(0)
                seen_to = self._size  # records below it have been listed
                src.adopt(self._x)
            placed: list[tuple[ShardMeta, int]] = []
            new_size = 0
            try:
                new.unlink_present()
                for rounds_left in range(self.CATCH_UP_ROUNDS - 1, -1, -1):
                    got, new_size = self._copy_records(src, new, todo, new_size)
                    placed += got
                    chaos.failpoint("blobnode.compact_copy")
                    with self._lock:
                        self._raise_if_closed()
                        if not rounds_left or self._size - seen_to <= self.SWAP_TAIL_MAX:
                            break  # the swap copies what is left, under the lock
                        todo = self._listed_from(seen_to)
                        seen_to = self._size
                        src.adopt(self._x)
                if not new.fds:
                    new.fd(0, create=True)  # nothing live: an empty generation
                new.sync()
                with trace.stage("chunk.compact_swap"), self._lock:
                    return self._swap_locked(new, placed, new_size, seen_to)
            except _ChunkClosed:
                # close() or destroy() came first: nothing is committed, and
                # nobody will open this chunk to sweep the orphans
                new.unlink_all()
                return 0
            except BaseException:
                new.close()  # the orphan files are swept on the next open
                raise
            finally:
                src.close()

    def _listed_from(self, offset: int) -> list[ShardMeta]:
        """Copies of the index entries at or past ``offset``, in file order
        (under the lock: the copy that follows reads them outside it)."""
        return sorted((ShardMeta(**m.__dict__) for m in self.shards.values()
                       if m.offset >= offset), key=lambda m: m.offset)

    def _raise_if_closed(self) -> None:
        if self._closed:
            raise _ChunkClosed(f"chunk {self.chunk_id} closed under its compaction")

    def _swap_locked(self, new: _Extents, placed: list, new_size: int, seen_to: int) -> int:
        self._raise_if_closed()
        # what was appended since the last listing: copied under the lock
        got, end = self._copy_records(self._x, new, self._listed_from(seen_to), new_size)
        if got:
            new.sync()  # no record is less durable in G+1 than it was in G
        new_metas: list[ShardMeta] = []
        garbage = []
        for old, at in placed + got:
            cur = self.shards.get(old.bid)
            if cur is None or cur.offset != old.offset:
                # deleted, lost or re-put while it was copied: its copy is
                # nobody's record in the new files
                garbage.append(ShardMeta(bid=old.bid, vuid=old.vuid, offset=at, size=old.size))
                continue
            new_metas.append(ShardMeta(bid=cur.bid, vuid=cur.vuid, offset=at,
                                       size=cur.size, status=cur.status))
        chaos.failpoint("blobnode.compact_commit")
        # commit point: gen bump + every re-offset meta, atomically.
        # Tombstones are RETAINED: they are cluster-level delete intent
        # ("deleted here, not lost"), not file-local garbage — purging
        # them would let the inspector resurrect a partially-deleted blob
        puts = [(self._gen_key(), str(new.gen).encode())]
        puts += [self._encode(m) for m in new_metas]
        self._db.write_batch(puts=puts)
        old_x, old_size, old_dead_held = self._x, self._size, self.held - self.live
        self.gen = new.gen
        self._x = new
        self._data_path = new.path()
        self._size = end
        self.shards = {m.bid: m for m in new_metas}
        old_x.unlink_all()
        # the new generation's account: the skipped ends of its extents were
        # never written; its garbage is given back where it can be
        self.holes = end - sum(_record_len(m) for m in new_metas)
        self.released = self.holes - sum(_record_len(g) for g in garbage)
        self._live_in, self._kept = {}, {}
        for m in new_metas + garbage:
            k = new.k(m.offset)
            self._live_in[k] = self._live_in.get(k, 0) + _record_len(m)
        for g in garbage:
            self._release_locked(g.offset, _record_len(g), counted=False)
        # what went back: the dead bytes the old files still held, and the
        # garbage (dead bytes this compaction wrote itself: counted as such,
        # so that holes made + garbage = given back + dead and still held)
        wrote_dead = sum(_record_len(g) for g in garbage)
        reg = registry("blobnode")
        reg.counter("compact_bytes", {"kind": "garbage"}).add(wrote_dead)
        reg.counter("released_bytes").add(old_dead_held + wrote_dead - (self.held - self.live))
        reg.counter("compact_total").add()
        reg.counter("compact_bytes", {"kind": "copied"}).add(end)
        reg.counter("compact_bytes", {"kind": "reclaimed"}).add(max(0, old_size - end))
        return old_size - end

    def tombstone(self, bid: int):
        """Record delete intent for a bid this chunk never stored (migrations
        carry tombstones with the unit). No-op when the bid is live here."""
        with self._lock:
            if bid in self.shards:
                return  # live here: a real delete must go through delete()
            meta = ShardMeta(bid=bid, vuid=0, offset=0, size=0,
                             status=STATUS_DELETED)
            self._log_idx(meta)
            self.tombstones.add(bid)

    def lose(self, bid: int):
        """Drop a record WITHOUT a tombstone — models media loss (a lost
        sector/file), as opposed to delete(), which records intent. The
        inspector repairs lost shards but finishes deleted ones."""
        with self._lock:
            meta = self.shards.pop(bid, None)
            if meta is None:
                raise NoSuchShard(f"chunk {self.chunk_id} bid {bid}")
            self._punch_locked(meta)
            self._db.delete(self._key(bid))

    def list_shards(self) -> list[ShardMeta]:
        with self._lock:
            return sorted(self.shards.values(), key=lambda m: m.bid)

    def destroy(self):
        """Delete the chunk outright: datafile, shard metas, tombstones, gen
        marker. Used when a volume unit is re-homed off this disk."""
        with self._lock:
            keys = [k for k, _ in self._db.scan(
                prefix=f"s/{self.chunk_id}/".encode())]
            keys.append(self._gen_key())
            self._db.write_batch(deletes=keys)
            self._closed = True
            self._x.unlink_all()
            self.shards.clear()
            self.tombstones.clear()

    def close(self):
        """Idempotent, under the lock: a descriptor's number is reused by the
        next open in the process, so it is closed once and never read after."""
        with self._lock:
            self._closed = True
            self._x.close()


class Disk:
    """A directory of chunks with a superblock (core/disk/superblock.go analog)."""

    DEFAULT_CHUNK_SIZE = 1 << 30

    def __init__(self, root: str, disk_id: int, chunk_size: int | None = None):
        self.root = root
        self.disk_id = disk_id
        self.chunk_size = chunk_size or self.DEFAULT_CHUNK_SIZE
        os.makedirs(os.path.join(root, "chunks"), exist_ok=True)
        self._sb_path = os.path.join(root, "superblock.json")
        self.metadb = open_kv(os.path.join(root, "metadb"))
        self._lock = SanitizedLock(name="blobnode.disk")
        self.chunks: dict[str, Chunk] = {}
        self._load()

    def _load(self):
        if os.path.exists(self._sb_path):
            with open(self._sb_path) as f:
                sb = json.load(f)
            self.disk_id = sb["disk_id"]
            self.chunk_size = sb["chunk_size"]
            for cid in sb["chunks"]:
                self.chunks[cid] = Chunk(
                    os.path.join(self.root, "chunks", cid), cid,
                    self.chunk_size, self.metadb
                )
        else:
            self._persist()

    def _persist(self):
        tmp = self._sb_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(
                {
                    "disk_id": self.disk_id,
                    "chunk_size": self.chunk_size,
                    "chunks": list(self.chunks),
                },
                f,
            )
        os.replace(tmp, self._sb_path)

    def create_chunk(self, chunk_id: str) -> Chunk:
        with self._lock:
            if chunk_id in self.chunks:
                return self.chunks[chunk_id]
            c = Chunk(os.path.join(self.root, "chunks", chunk_id), chunk_id,
                      self.chunk_size, self.metadb)
            self.chunks[chunk_id] = c
            self._persist()
            return c

    def stats(self) -> dict:
        return {
            "disk_id": self.disk_id,
            "chunks": len(self.chunks),
            # the datafiles' lengths (KEEP_SIZE leaves a length as it was,
            # and a dropped extent does too) ...
            "used": sum(c.used for c in self.chunks.values()),
            # ... and what the filesystem holds of them: less every byte a
            # punch, a dropped extent or a compaction really gave back
            "held": sum(c.held for c in self.chunks.values()),
        }

    def close(self):
        for c in self.chunks.values():
            c.close()
        self.metadb.close()


_NODES: "weakref.WeakSet[BlobNode]" = weakref.WeakSet()


def _collect_held() -> None:
    """cfs_blobnode_held_bytes: what the filesystem holds of every open
    chunk's datafile in this process, summed at the scrape (a level: it falls
    when space really comes back, which `used`, a sum of lengths, never does)."""
    held = 0
    for node in list(_NODES):
        for disk in list(node.disks.values()):
            held += sum(c.held for c in list(disk.chunks.values()))
    registry("blobnode").gauge("held_bytes").set(held)


exporter.add_collector(_collect_held)


class BlobNode:
    """Shard API over a set of disks (api/blobnode PutShard/GetShard analog).

    vuid (volume-unit id) identifies one stripe position of one volume; the
    clustermgr maps vuid -> (node, disk, chunk).
    """

    def __init__(self, node_id: int, disk_roots: list[str],
                 iostat: bool = False, scrub_rate: float | None = None,
                 cm=None):
        self.node_id = node_id
        # the cluster manager whose disk table says which of this node's disks
        # still serve (_refuse_unless_normal); None: a bare engine serves all
        self._cm = cm
        self.disks: dict[int, Disk] = {}
        for i, root in enumerate(disk_roots):
            d = Disk(root, disk_id=node_id * 1000 + i)
            self.disks[d.disk_id] = d
        self._chunk_of_vuid: dict[int, tuple[int, str]] = {}
        self._lock = SanitizedLock(name="blobnode.node")
        # shard-IO observability: per-node TP metrics in the blobnode role
        # registry; optionally the mmap'd iostat block node-side viewers read
        # (common/iostat) — off by default so test fleets don't litter shm
        from chubaofs_tpu.utils.exporter import registry as _registry

        self._reg = _registry("blobnode")
        # the reclaim plane's counters, made here at 0: a reader of "nothing
        # was punched" must find a series that says 0, not none
        for name in ("shard_delete", "hole_bytes", "punched_bytes", "punch_failed",
                     "released_bytes", "extents_dropped", "compact_total"):
            self._reg.counter(name)
        _NODES.add(self)  # cfs_blobnode_held_bytes sums them at the scrape
        for kind in ("copied", "reclaimed", "garbage"):
            self._reg.counter("compact_bytes", {"kind": kind})
        self._iostat = None
        if iostat:
            from chubaofs_tpu.blobstore.iostat import IOStat

            self._iostat = IOStat(f"blobnode-{node_id}")
        # recover vuid->chunk mapping from chunk names ("vuid-<id>")
        for d in self.disks.values():
            for cid in d.chunks:
                if cid.startswith("vuid-"):
                    self._chunk_of_vuid[int(cid[5:])] = (d.disk_id, cid)
        # -- detection state (datainspect.go + disk-failure reporting) -------
        # scrub: token-bucket byte budget (CFS_SCRUB_RATE bytes/s; 0 =
        # unlimited) + a resumable (vuid, bid) cursor persisted in the first
        # disk's metadb, so a restarted node continues mid-sweep instead of
        # rescanning from shard zero
        if scrub_rate is None:
            scrub_rate = float(os.environ.get("CFS_SCRUB_RATE",
                                              str(64 << 20)))
        self._scrub_bucket = None
        if scrub_rate > 0:
            from chubaofs_tpu.utils.ratelimit import TokenBucket

            self._scrub_bucket = TokenBucket(scrub_rate)
        self._scrub_db = (self.disks[min(self.disks)].metadb
                          if self.disks else None)
        self._scrub_cursor: tuple[int, int] | None = None
        if self._scrub_db is not None:
            raw = self._scrub_db.get(b"scrub/cursor")
            if raw:
                try:
                    v, b = json.loads(raw)
                    self._scrub_cursor = (int(v), int(b))
                except (ValueError, TypeError):
                    # bad JSON raises ValueError, but valid-JSON garbage (a
                    # scalar, an object) fails the unpack with TypeError —
                    # either way: restart the sweep, lose nothing
                    pass
        # consecutive IO errors per disk: the heartbeat's disk-failure signal
        self._io_errors: dict[int, int] = {}
        self._closed = False

    # -- chunk lifecycle (clustermgr drives this) ---------------------------

    def create_vuid(self, vuid: int, disk_id: int | None = None) -> int:
        """Bind a volume unit to a fresh chunk; returns the disk id used."""
        with self._lock:
            if vuid in self._chunk_of_vuid:
                return self._chunk_of_vuid[vuid][0]
            if disk_id is None:
                disk_id = min(
                    self.disks, key=lambda d: self.disks[d].stats()["used"]
                )
            self.disks[disk_id].create_chunk(f"vuid-{vuid}")
            self._chunk_of_vuid[vuid] = (disk_id, f"vuid-{vuid}")
            return disk_id

    def _chunk(self, vuid: int) -> Chunk:
        loc = self._chunk_of_vuid.get(vuid)
        if loc is None:
            raise NoSuchShard(f"vuid {vuid} not on node {self.node_id}")
        disk_id, cid = loc
        return self.disks[disk_id].chunks[cid]

    def _serves(self, disk_id: int) -> bool:
        """The cluster manager holds this disk NORMAL (cm.disk_serves: one
        dict read, no lock, no tick to wait for); a bare engine serves all."""
        return self._cm is None or self._cm.disk_serves(disk_id)

    def _refuse_unless_normal(self, vuid: int) -> None:
        """The entry gate of every shard call: a chunk on a disk the cluster
        manager holds BROKEN (the operator's declaration, this node's own
        io_errors report) or DROPPED is refused before any file is touched,
        from the moment the status is set. The node's other disks serve as
        before, and a disk set back to NORMAL serves again. The node's own
        sweeps (scrub, inspect, compaction) pass such a disk by."""
        loc = self._chunk_of_vuid.get(vuid)
        if loc is None:
            return  # no chunk here: _chunk says so
        if not self._serves(loc[0]):
            self._reg.counter("io_refused", {"reason": "disk_broken"}).add()
            raise DiskBroken(f"disk {loc[0]} is not NORMAL: no I/O for vuid {vuid}")

    def _disk_io(self, vuid: int, op):
        """Run one chunk op tracking CONSECUTIVE per-disk OSErrors — the
        disk-failure signal heartbeat() reports to clustermgr. Logical
        faults (NoSuchShard, CRC mismatches) don't count: a dying device
        shows up as the OS refusing IO, not as absent bids."""
        loc = self._chunk_of_vuid.get(vuid)
        before = self._io_errors.get(loc[0], 0) if loc is not None else 0
        try:
            out = op()
        except OSError:
            if loc is not None:
                # under the node lock: concurrent failing reads (access
                # fan-out, repair pool, scrub) must not lose increments of
                # the CONSECUTIVE count heartbeat's broken_after gates on
                with self._lock:
                    self._io_errors[loc[0]] = \
                        self._io_errors.get(loc[0], 0) + 1
                self._reg.counter("disk_io_errors").add()
            raise
        if loc is not None and before:
            with self._lock:
                # a success breaks the consecutive chain — but only reset if
                # the count is still the one we snapshotted: failures that
                # landed WHILE this op was in flight are newer information,
                # and zeroing them would lose increments the except path
                # took the lock to keep
                if self._io_errors.get(loc[0], 0) == before:
                    self._io_errors[loc[0]] = 0
        return out

    # -- shard API ----------------------------------------------------------

    def put_shard(self, vuid: int, bid: int, payload: bytes) -> None:
        import time as _time

        self._refuse_unless_normal(vuid)
        t0 = _time.perf_counter()
        if self._iostat is not None:
            self._iostat.write_begin()
        try:
            with self._reg.tp("shard_put"), trace.mark("blobnode.put_shard"):
                chaos.failpoint("blobnode.put_shard", node=self.node_id)
                # corrupt-on-write models a bad controller: the framing CRCs
                # the already-flipped bytes, so only a later stripe-level
                # repair catches it
                payload = chaos.corrupt_bytes("blobnode.put_shard.payload",
                                              payload, node=self.node_id)
                self._disk_io(
                    vuid, lambda: self._chunk(vuid).put(bid, vuid, payload))
            self._reg.counter("shard_put_bytes_total").add(len(payload))
        finally:
            if self._iostat is not None:
                self._iostat.write_done(
                    len(payload), int((_time.perf_counter() - t0) * 1e6))

    def get_shard(self, vuid: int, bid: int, offset: int = 0, size: int | None = None) -> bytes:
        import time as _time

        self._refuse_unless_normal(vuid)
        t0 = _time.perf_counter()
        data = b""
        if self._iostat is not None:
            self._iostat.read_begin()
        try:
            with self._reg.tp("shard_get"), trace.mark("blobnode.get_shard"):
                chaos.failpoint("blobnode.get_shard", node=self.node_id)
                data = self._disk_io(
                    vuid, lambda: self._chunk(vuid).get(bid, offset, size))
            self._reg.counter("shard_get_bytes_total").add(len(data))
            # corrupt-on-read models wire/DMA corruption past the CRC framing
            return chaos.corrupt_bytes("blobnode.get_shard.data", data,
                                       node=self.node_id)
        finally:
            if self._iostat is not None:
                self._iostat.read_done(
                    len(data), int((_time.perf_counter() - t0) * 1e6))

    def get_shard_combined(self, vuid: int, bid: int, coeffs: bytes) -> bytes:
        """Beta-combine helper read for regenerating-code repair: read the
        whole local shard, combine its len(coeffs) equal sub-units with the
        failed shard's GF(2^8) coefficients (codec/pm.py helper math), and
        return the single shard/len(coeffs)-byte payload. The disk still
        reads the full shard (iostat shows that truth); what shrinks is the
        bytes shipped to the repair worker — the cross-node cost repair
        bandwidth actually pays.
        """
        import time as _time

        import numpy as np

        from chubaofs_tpu.ops import gf256

        self._refuse_unless_normal(vuid)
        t0 = _time.perf_counter()
        data = b""
        if self._iostat is not None:
            self._iostat.read_begin()
        try:
            with self._reg.tp("shard_get"), trace.mark("blobnode.get_shard"):
                # same failpoint as get_shard: wire-delay/error chaos regimes
                # apply to beta reads and full reads alike
                chaos.failpoint("blobnode.get_shard", node=self.node_id)
                data = self._disk_io(
                    vuid, lambda: self._chunk(vuid).get(bid, 0, None))
            buf = np.frombuffer(data, np.uint8)
            if not coeffs or buf.size % len(coeffs):
                raise BlobNodeError(
                    f"shard {len(data)}B not divisible into "
                    f"{len(coeffs)} sub-units")
            phi = np.frombuffer(coeffs, np.uint8)[None, :]
            out = gf256.gf_matmul(phi, buf.reshape(len(coeffs), -1)).tobytes()
            # count the SHIPPED bytes, like get_shard does — the beta win
            # must be visible in the node's own byte counters
            self._reg.counter("shard_get_bytes_total").add(len(out))
            self._reg.counter("shard_combine_bytes_total").add(len(out))
            return chaos.corrupt_bytes("blobnode.get_shard.data", out,
                                       node=self.node_id)
        finally:
            if self._iostat is not None:
                # the disk truly read the whole shard; iostat records that
                self._iostat.read_done(
                    len(data), int((_time.perf_counter() - t0) * 1e6))

    def mark_delete_shard(self, vuid: int, bid: int) -> None:
        self._refuse_unless_normal(vuid)
        self._chunk(vuid).mark_delete(bid)

    def delete_shard(self, vuid: int, bid: int) -> None:
        if not self.delete_shards(vuid, (bid,)):
            raise NoSuchShard(f"vuid {vuid} bid {bid}")

    def mark_delete_shards(self, vuid: int, bids) -> int:
        """Phase one of a delete for a unit's BATCH of bids: one take of the
        chunk lock, one metadb batch. Returns how many the chunk held."""
        self._refuse_unless_normal(vuid)
        return self._chunk(vuid).mark_delete_batch(bids)

    def delete_shards(self, vuid: int, bids) -> int:
        """Phase two for the batch: punch out, tombstone. Returns how many
        records went (cfs_blobnode_shard_delete counts them)."""
        self._refuse_unless_normal(vuid)
        n = self._chunk(vuid).delete_batch(bids)
        if n:
            self._reg.counter("shard_delete").add(n)
        return n

    def list_shards(self, vuid: int) -> list[ShardMeta]:
        self._refuse_unless_normal(vuid)
        return self._chunk(vuid).list_shards()

    def lose_shard(self, vuid: int, bid: int) -> None:
        """Simulate media loss of one shard (no delete tombstone)."""
        self._chunk(vuid).lose(bid)

    def tombstone_shard(self, vuid: int, bid: int) -> None:
        """Record delete intent for a bid this chunk never stored — migrations
        carry tombstones WITH the unit, or a partially-deleted blob would be
        resurrected once the only tombstone-holding chunk moves."""
        self._chunk(vuid).tombstone(bid)

    def tombstones_of(self, vuid: int) -> set[int]:
        """All tombstoned bids of one unit (migrations enumerate these)."""
        return set(self._chunk(vuid).tombstones)

    def drop_vuid(self, vuid: int) -> None:
        """Release a re-homed volume unit's chunk: the space a balance/migrate
        moved away must actually free on the source disk. Idempotent."""
        with self._lock:
            loc = self._chunk_of_vuid.pop(vuid, None)
        if loc is None:
            return
        disk_id, cid = loc
        disk = self.disks[disk_id]
        with disk._lock:
            chunk = disk.chunks.pop(cid, None)
        if chunk is not None:
            chunk.destroy()
            disk._persist()

    def has_tombstone(self, vuid: int, bid: int) -> bool:
        """True when this bid was DELETED here (vs never written / lost)."""
        try:
            return bid in self._chunk(vuid).tombstones
        except NoSuchShard:
            return False

    def stats(self) -> dict:
        return {
            "node_id": self.node_id,
            "disks": [d.stats() for d in self.disks.values()],
        }

    # -- background hygiene (core compaction + datainspect.go analogs) -------

    # The compaction rule, a function of a chunk's bytes alone. A chunk is
    # rewritten when it is (a) LARGE and MOSTLY EMPTY: where punch-out has
    # given a deleted record's blocks back at once, what a compaction buys is
    # a shorter file and a smaller index, not free space, and at a hole share
    # of 0.8 it copies at most one byte for every four it drops. Upstream's
    # blobnode (core/chunk compaction, its configuration's
    # compact_empty_rate_threshold 0.8 with compact_min_size_threshold 16 GiB,
    # its chunk size; or a file past compact_trigger_threshold 1 TiB) compacts
    # a chunk only once it is full-sized and four fifths empty; a chunk here
    # is 1 GiB, so "large" is half of that. Or (b) it HOLDS more dead bytes
    # than live ones: on a filesystem that refuses the punch a dead record is
    # space still to reclaim, what its dying extents have not given back
    # (Chunk.held - Chunk.live) is what a compaction frees, and it copies at
    # most one byte for every byte it frees.
    COMPACT_MIN_HOLE_RATIO = 0.8
    COMPACT_MIN_DEAD_HELD = 64 << 20

    def compaction_candidates(self):
        """The chunks the rule picks now, one after the other (a caller that
        compacts one and looks again sees the rule applied afresh)."""
        for disk in self.disks.values():
            if not self._serves(disk.disk_id):
                continue
            for chunk in list(disk.chunks.values()):
                dead_held = chunk.held - chunk.live
                if (chunk.used >= chunk.max_size // 2
                        and chunk.holes >= self.COMPACT_MIN_HOLE_RATIO * chunk.used) \
                        or (dead_held >= self.COMPACT_MIN_DEAD_HELD and dead_held >= chunk.live):
                    yield chunk

    def compact_once(self) -> int:
        """Compact every chunk the rule picks; returns total bytes reclaimed."""
        return sum(chunk.compact() for chunk in self.compaction_candidates())

    def inspect_once(self) -> list[tuple[int, int]]:
        """CRC scrub (blobnode/datainspect.go): re-read every live shard
        through the crc32block framing; returns [(vuid, bid)] that fail.
        The one-shot full sweep; the production loop is scrub_once()."""
        bad: list[tuple[int, int]] = []
        for vuid, (disk_id, cid) in list(self._chunk_of_vuid.items()):
            chunk = self.disks[disk_id].chunks.get(cid)
            if chunk is None or not self._serves(disk_id):
                continue
            for meta in chunk.list_shards():
                if meta.status != STATUS_NORMAL:
                    continue
                try:
                    chunk.get(meta.bid)
                except NoSuchShard:
                    pass  # deleted since it was listed: not a finding
                except Exception:
                    bad.append((vuid, meta.bid))
        return bad

    def _scrub_positions(self, cur: tuple[int, int] | None):
        """Live shard positions strictly AFTER the cursor, chunk by chunk
        in (vuid, bid) order — the batched-per-chunk iteration scrub_once
        resumes through."""
        for vuid in sorted(self._chunk_of_vuid):
            if cur is not None and vuid < cur[0]:
                continue
            loc = self._chunk_of_vuid.get(vuid)
            if loc is None:
                continue
            chunk = self.disks[loc[0]].chunks.get(loc[1])
            if chunk is None or not self._serves(loc[0]):
                continue
            for meta in chunk.list_shards():
                if cur is not None and vuid == cur[0] and meta.bid <= cur[1]:
                    continue
                if meta.status == STATUS_NORMAL:
                    yield vuid, meta.bid, chunk, meta

    def _save_scrub_cursor(self) -> None:
        if self._scrub_db is None:
            return
        try:
            if self._scrub_cursor is None:
                self._scrub_db.delete(b"scrub/cursor")
            else:
                self._scrub_db.put(b"scrub/cursor",
                                   json.dumps(list(self._scrub_cursor)).encode())
        except Exception:
            pass  # a cursor that fails to persist restarts the sweep, no worse

    def scrub_once(self, max_shards: int = 256) -> dict:
        """One budgeted tick of the background CRC scrub loop: re-read up to
        max_shards live shards through their crc32block framing, resuming
        from the persisted cursor, spending at most the CFS_SCRUB_RATE
        token-bucket byte budget. Returns {"scanned", "bad": [(vuid, bid)],
        "complete"} — complete=True means the sweep wrapped (the cursor
        reset) and everything currently live was verified this cycle."""
        scanned = 0
        bad: list[tuple[int, int]] = []
        complete = False
        exhausted = True  # ran off the end of the shard list (vs budget)
        for vuid, bid, chunk, meta in self._scrub_positions(self._scrub_cursor):
            if scanned >= max_shards:
                exhausted = False
                break
            cost = HEADER_LEN + crc32block.encoded_len(meta.size)
            if self._scrub_bucket is not None and not \
                    self._scrub_bucket.try_acquire(
                        min(cost, self._scrub_bucket.burst)):
                exhausted = False  # byte budget dry: resume here next tick
                break
            try:
                self._disk_io(vuid, lambda: chunk.get(bid))
            except OSError:
                # the OS refusing IO is a DISK failure (heartbeat's
                # consecutive-error signal, counted by _disk_io), not
                # bitrot — repairing shard-by-shard off a dying device
                # would fight the disk-repair migration
                pass
            except NoSuchShard:
                pass  # deleted since it was listed (the deleter runs beside the scrub): not bitrot
            except Exception:
                bad.append((vuid, bid))
            scanned += 1
            self._scrub_cursor = (vuid, bid)
        if exhausted:
            # wrapped: a full pass over every live shard finished
            if self._scrub_cursor is not None:
                self._reg.counter("scrub_sweeps").add()
            complete = True
            self._scrub_cursor = None
        self._save_scrub_cursor()
        if scanned:
            self._reg.counter("scrub_scanned_shards").add(scanned)
        if bad:
            self._reg.counter("scrub_bad_shards").add(len(bad))
            # a finding is a TRANSITION (healthy bytes -> detected bitrot):
            # one timeline record per tick, the shard ids in the detail —
            # never a metric label (obslint rule 1)
            from chubaofs_tpu.utils import events

            events.emit("scrub_finding", events.SEV_WARNING,
                        entity=f"node{self.node_id}",
                        detail={"node_id": self.node_id,
                                "bad": [[v, b] for v, b in bad],
                                "scanned": scanned})
        return {"scanned": scanned, "bad": bad, "complete": complete}

    def heartbeat(self, cm, broken_after: int = 3) -> None:
        """Report per-disk liveness + chunk counts to clustermgr, flagging
        any disk whose consecutive IO-error count crossed broken_after as
        BROKEN (the disk-failure half of detection; heartbeats going SILENT
        — a dead process — is caught by the clustermgr-side expiry)."""
        if self._closed:
            # a dead engine must go SILENT: heartbeat itself touches no disk
            # IO, so without this gate a crashed-but-still-routed node (the
            # chaos crash plan closes the engine in place) would keep
            # beating and the expiry path could never detect it
            return
        for disk_id, disk in self.disks.items():
            if self._io_errors.get(disk_id, 0) >= broken_after:
                try:
                    # only flip a NORMAL disk: re-reporting a DROPPED disk
                    # (repair done, error count never reset) as broken would
                    # mint an endless broken->repair->dropped->broken cycle
                    if cm.disk_status(disk_id) == DISK_NORMAL:
                        cm.set_disk_status(disk_id, DISK_BROKEN,
                                           reason="io_errors")
                except Exception:
                    pass  # control plane unreachable: retried next beat
                continue  # a broken disk stops heartbeating as healthy
            try:
                # no chunk_count: clustermgr's unit accounting is
                # authoritative (physical chunks lag volume creation)
                cm.heartbeat_disk(disk_id)
            except Exception:
                pass

    def close(self):
        self._closed = True
        for d in self.disks.values():
            d.close()
        if self._iostat is not None:
            self._iostat.close()
