"""A chunk is a descriptor with positional I/O (ISSUE 28): what `Chunk.put`
leaves in the datafile and what `Chunk.get` makes of it, under both engines,
held to the inline framing loop and to the parent's buffered file; what a
damaged or truncated file raises; puts, gets, compaction, delete and destroy
at once on one chunk."""

import errno
import json
import os
import resource
import struct
import sys
import threading
import time
import zlib

import pytest

from chubaofs_tpu.blobstore.blobnode import (HEADER_LEN, MAGIC, BlobNode, ChunkFull, NoSuchShard,
                                             ShardMeta)
from chubaofs_tpu.blobstore.clustermgr import make_vuid
from chubaofs_tpu.utils import crc32block, exporter, kvstore
from chubaofs_tpu.utils.crc32block import BLOCK_SIZE, CrcError

from test_crc32block import counts, force_python, grown, payload_of

# one block zlib checks holding the lock / the first native size / one block /
# a second block of one byte / az2's shard (exactly four) / az1's / az3's
SIZES = [1, 5120, 5121, 65536, 65537, 262144, 349526, 699051]
STRIDE = BLOCK_SIZE + 4
VUID = make_vuid(1, 0)
BID = 7


@pytest.fixture(params=["native", "python"])
def engine(request, monkeypatch):
    """Both engines: `python` is a host where libcfskv cannot be built."""
    if request.param == "python":
        force_python(monkeypatch)
    elif kvstore._load_native() is None:
        pytest.skip("libcfskv cannot be built here: there is no native engine to test")
    return request.param


def engine_of(n: int, engine: str) -> str:
    """Who writes and reads a shard of n bytes: the 5 KiB rule, then the host."""
    return "python" if n <= 5120 else engine


@pytest.fixture
def node(tmp_path):
    node = BlobNode(node_id=1, disk_roots=[str(tmp_path / "d0")], scrub_rate=0)
    node.create_vuid(VUID)
    yield node
    node.close()


def record_of(bid: int, vuid: int, payload: bytes) -> bytes:
    """A shard record by the definition: header, its crc, the payload framed by the loop."""
    head = struct.pack("<IQQQ", MAGIC, bid, vuid, len(payload))
    return head + struct.pack("<I", zlib.crc32(head)) + crc32block.encode(payload)


def file_bytes(path: str) -> bytes:
    with open(path, "rb") as f:
        return f.read()


def flip(path: str, at: int) -> None:
    with open(path, "r+b") as f:
        f.seek(at)
        byte = f.read(1)
        f.seek(at)
        f.write(bytes([byte[0] ^ 0x20]))


def io_errors() -> float:
    return exporter.registry("blobnode").counter("disk_io_errors").value


@pytest.mark.parametrize("n", SIZES)
def test_put_leaves_the_reference_record_in_the_file(node, engine, n):
    """The datafile is header + inline framing, byte for byte, record after
    record, and each put counts once under the engine that wrote it."""
    first, second = payload_of(n, seed=n), payload_of(n, seed=n + 1)
    before = counts()
    node.put_shard(VUID, BID, first)
    assert grown(before) == {(engine_of(n, engine), "frame"): 1}
    node.put_shard(VUID, BID + 1, second)
    chunk = node._chunk(VUID)
    want = record_of(BID, VUID, first) + record_of(BID + 1, VUID, second)
    assert file_bytes(chunk._data_path) == want
    assert chunk.used == len(want) == 2 * (HEADER_LEN + crc32block.encoded_len(n))
    assert chunk.shards[BID + 1].offset == len(want) // 2


@pytest.mark.parametrize("n", SIZES)
def test_full_and_ranged_get_are_slices_of_the_payload(node, engine, n):
    payload = payload_of(n, seed=n)
    node.put_shard(VUID, BID - 1, payload_of(1000))  # the record does not start the file
    node.put_shard(VUID, BID, payload)
    before = counts()
    assert node.get_shard(VUID, BID) == payload
    assert grown(before) == {(engine_of(n, engine), "verify"): 1}
    edges = sorted({0, 1, n // 2, BLOCK_SIZE - 1, BLOCK_SIZE, BLOCK_SIZE + 1, n - 1, n})
    for lo in (e for e in edges if e <= n):
        for hi in (e for e in edges if lo <= e <= n):
            assert node.get_shard(VUID, BID, offset=lo, size=hi - lo) == payload[lo:hi], (lo, hi)
    # a ranged read takes the covering blocks only: a block beyond it may be rotten
    if n > BLOCK_SIZE:
        chunk = node._chunk(VUID)
        flip(chunk._data_path, chunk.shards[BID].offset + HEADER_LEN + STRIDE + 3)
        assert node.get_shard(VUID, BID, offset=10, size=BLOCK_SIZE - 10) == payload[10:BLOCK_SIZE]
        with pytest.raises(CrcError):
            node.get_shard(VUID, BID, offset=10, size=BLOCK_SIZE - 9)  # one byte of block 1


@pytest.mark.parametrize("n", SIZES)
def test_flipped_byte_names_its_block(node, engine, n):
    """A flip in a block's body, then in its crc word: CrcError with the framed
    offset of that block, for every block of the shard; not a disk error."""
    payload = payload_of(n, seed=n)
    node.put_shard(VUID, BID, payload)
    chunk = node._chunk(VUID)
    base = chunk.shards[BID].offset + HEADER_LEN
    errors = io_errors()
    for block in range(-(-n // BLOCK_SIZE)):
        length = min(BLOCK_SIZE, n - block * BLOCK_SIZE)
        for at in (block * STRIDE + length // 2, block * STRIDE + length + 2):
            flip(chunk._data_path, base + at)
            with pytest.raises(CrcError, match=f"framed offset {block * STRIDE}$"):
                node.get_shard(VUID, BID)
            flip(chunk._data_path, base + at)
    assert node.get_shard(VUID, BID) == payload and io_errors() == errors


@pytest.mark.parametrize("n", SIZES)
def test_truncated_datafile_is_a_disk_error_never_a_short_payload(node, engine, n):
    payload = payload_of(n, seed=n)
    node.put_shard(VUID, BID, payload)
    chunk = node._chunk(VUID)
    framed = crc32block.encoded_len(n)
    disk_id = node._chunk_of_vuid[VUID][0]
    for keep in sorted({framed - 1, framed - 4, framed // 2, 0}, reverse=True):
        os.truncate(chunk._data_path, chunk.shards[BID].offset + HEADER_LEN + keep)
        errors, streak = io_errors(), node._io_errors.get(disk_id, 0)
        with pytest.raises(OSError, match="short read") as e:
            node.get_shard(VUID, BID)
        assert e.value.errno == errno.EIO
        assert io_errors() == errors + 1 and node._io_errors[disk_id] == streak + 1
    # the part that is still there reads, where the range lies in whole blocks of it
    if n > BLOCK_SIZE:
        with open(chunk._data_path, "r+b") as f:
            f.seek(chunk.shards[BID].offset)
            f.write(record_of(BID, VUID, payload)[:HEADER_LEN + STRIDE])
        assert node.get_shard(VUID, BID, offset=5, size=100) == payload[5:105]
        assert node._io_errors[disk_id] == 0  # a success ends the streak


def parent_put(root: str, chunk, bid: int, payload: bytes) -> None:
    """`Chunk.put` as the parent commit did it, on a closed node's files: the
    record framed in memory, `seek` + `write` + `flush` of a buffered file,
    the meta into the metadb."""
    record = record_of(bid, VUID, payload)
    offset = os.path.getsize(chunk._data_path)
    with open(chunk._data_path, "r+b") as f:
        f.seek(offset)
        f.write(record)
        f.flush()
    db = kvstore.open_kv(os.path.join(root, "metadb"))
    meta = ShardMeta(bid=bid, vuid=VUID, offset=offset, size=len(payload))
    db.put(chunk._key(bid), json.dumps(meta.__dict__).encode())
    db.close()


def parent_get(chunk, bid: int, offset: int, size: int) -> bytes:
    """`Chunk.get` as the parent did it: buffered `seek` + `read`, `decode`."""
    meta = chunk.shards[bid]
    fstart, fend = crc32block.block_range(offset, size)
    with open(chunk._data_path, "r+b") as f:
        f.seek(meta.offset + HEADER_LEN + fstart)
        framed = f.read(min(fend, crc32block.encoded_len(meta.size)) - fstart)
    inner = offset - fstart // STRIDE * BLOCK_SIZE
    return crc32block.decode(framed)[inner:inner + size]


@pytest.mark.parametrize("n", SIZES)
def test_parent_and_change_read_each_others_records(tmp_path, engine, n):
    root = str(tmp_path / "d0")
    ours, theirs = payload_of(n, seed=1), payload_of(n, seed=2)
    node = BlobNode(node_id=1, disk_roots=[root], scrub_rate=0)
    node.create_vuid(VUID)
    node.put_shard(VUID, 1, ours)
    chunk = node._chunk(VUID)
    lo, hi = n // 3, n - n // 4
    assert parent_get(chunk, 1, 0, n) == ours and parent_get(chunk, 1, lo, hi - lo) == ours[lo:hi]
    node.close()
    parent_put(root, chunk, 2, theirs)
    node = BlobNode(node_id=1, disk_roots=[root], scrub_rate=0)
    assert node.get_shard(VUID, 2) == theirs and node.get_shard(VUID, 1) == ours
    assert node.get_shard(VUID, 2, offset=lo, size=hi - lo) == theirs[lo:hi]
    node.put_shard(VUID, 3, ours)  # appended after the parent's record, not over it
    assert [node.get_shard(VUID, b) for b in (1, 2, 3)] == [ours, theirs, ours]
    assert node.inspect_once() == []
    node.close()


@pytest.mark.parametrize("n", [2048, 349526])
def test_os_refusal_keeps_its_errno(engine, n):
    """-errno from the native call is raised as the OSError the os module
    would raise: a full device on write, a closed descriptor on read."""
    fd = os.open("/dev/full", os.O_RDWR)
    try:
        with pytest.raises(OSError) as e:
            crc32block.pwrite(fd, 0, payload_of(n), prefix=b"h" * 32)
        assert e.value.errno == errno.ENOSPC
    finally:
        os.close(fd)
    with pytest.raises(OSError) as e:
        crc32block.pread(-1, 0, crc32block.encoded_len(n))
    assert e.value.errno == errno.EBADF


@pytest.mark.parametrize("n", [2048, 349526])
def test_write_cut_short_is_resumed_then_raised(tmp_path, engine, n):
    """The file size limit cuts the first write short at the limit; the
    resumed one is refused: the error is raised, nothing is reported written."""
    limit = crc32block.encoded_len(n) // 2
    soft, hard = resource.getrlimit(resource.RLIMIT_FSIZE)
    fd = os.open(str(tmp_path / "f"), os.O_RDWR | os.O_CREAT)
    try:
        resource.setrlimit(resource.RLIMIT_FSIZE, (limit, hard))
        with pytest.raises(OSError) as e:
            crc32block.pwrite(fd, 0, payload_of(n))
    finally:
        resource.setrlimit(resource.RLIMIT_FSIZE, (soft, hard))
        os.close(fd)
    assert e.value.errno == errno.EFBIG
    assert file_bytes(str(tmp_path / "f")) == crc32block.encode(payload_of(n))[:limit]


def test_chunk_full_is_decided_before_any_write(tmp_path, engine):
    node = BlobNode(node_id=1, disk_roots=[str(tmp_path / "d0")])
    node.disks[1000].chunk_size = 400_000
    node.create_vuid(VUID)
    node.put_shard(VUID, 1, payload_of(349526))
    chunk = node._chunk(VUID)
    size, before = os.path.getsize(chunk._data_path), counts()
    with pytest.raises(ChunkFull):
        node.put_shard(VUID, 2, payload_of(100_000))
    assert os.path.getsize(chunk._data_path) == size == chunk.used
    assert grown(before) == {} and 2 not in chunk.shards
    node.close()


def test_closed_chunk_refuses_io_as_a_disk_error(node):
    """close() is idempotent and a read after it is the OS refusing a bad
    descriptor: counted, never a read of whatever file took the number."""
    node.put_shard(VUID, BID, payload_of(349526))
    chunk = node._chunk(VUID)
    chunk.close()
    chunk.close()
    errors = io_errors()
    with pytest.raises(OSError) as e:
        node.get_shard(VUID, BID)
    assert e.value.errno == errno.EBADF and io_errors() == errors + 1
    with pytest.raises(OSError):
        node.put_shard(VUID, BID + 1, payload_of(349526))
    assert BID + 1 not in chunk.shards


def run_threads(targets, seconds: float = 60):
    """Start, join with a deadline, hand over what any of them raised."""
    raised = []

    def guard(fn):
        def run():
            try:
                fn()
            except BaseException as e:  # noqa: BLE001 (handed to the test's assert)
                raised.append(e)
        return run

    keep = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=guard(t)) for t in targets]
        for t in threads:
            t.start()
        deadline = time.monotonic() + seconds
        for t in threads:
            t.join(timeout=max(0.1, deadline - time.monotonic()))
    finally:
        sys.setswitchinterval(keep)
    assert not any(t.is_alive() for t in threads)
    return raised


def test_sixteen_writers_and_eight_readers_share_one_chunk(node, engine):
    """Records land at disjoint offsets, every shard reads back equal while
    the others are still being written, and `used` is the sum of the records."""
    writers, readers, each = 16, 8, 6
    sizes = [349526, 2048, 70000, 262144, 5121, 1]
    payload = {(w, k): payload_of(sizes[(w + k) % len(sizes)], seed=w * 100 + k)
               for w in range(writers) for k in range(each)}
    done, lock = [], threading.Lock()
    chunk = node._chunk(VUID)

    def write(w):
        for k in range(each):
            node.put_shard(VUID, w * 100 + k, payload[w, k])
            with lock:
                done.append((w, k))

    def read():
        seen = 0
        while seen < writers * each:
            with lock:
                ready = list(done)
            for w, k in ready[seen:]:
                assert node.get_shard(VUID, w * 100 + k) == payload[w, k]
            seen = len(ready)

    assert run_threads([lambda w=w: write(w) for w in range(writers)] + [read] * readers) == []
    metas = sorted(chunk.shards.values(), key=lambda m: m.offset)
    at = 0
    for m in metas:  # back to back: no gap, no overlap
        assert m.offset == at
        at += HEADER_LEN + crc32block.encoded_len(m.size)
    assert len(metas) == writers * each and chunk.used == at == os.path.getsize(chunk._data_path)
    for (w, k), p in payload.items():
        assert node.get_shard(VUID, w * 100 + k) == p
    assert node.inspect_once() == []


@pytest.mark.parametrize("what", ["compact", "delete", "destroy"])
def test_get_against_chunk_surgery_in_flight(node, engine, what):
    """Readers hammer a chunk while it is compacted (the file and the
    descriptor swapped under them), its shards deleted (holes punched), or the
    whole chunk destroyed: a get returns the exact payload or says the shard
    is gone; never other bytes, never a CRC error, never a bad descriptor."""
    bids = list(range(1, 25))
    payload = {b: payload_of([349526, 70000, 2048][b % 3], seed=b) for b in bids}
    for b in bids:
        node.put_shard(VUID, b, payload[b])
    chunk = node._chunk(VUID)
    stop = threading.Event()
    gone, reads = set(), [0]

    def read(i):
        k = i
        while not stop.is_set():
            b = bids[k % len(bids)]
            k += 7
            try:
                got = chunk.get(b)
            except NoSuchShard:
                assert b in gone or what == "destroy"
                continue
            assert got == payload[b]
            reads[0] += 1

    def operate():
        try:
            time.sleep(0.05)
            if what == "compact":
                for b in bids[::4]:
                    chunk.delete(b)  # holes to reclaim; not read below
                for _ in range(12):
                    chunk.compact()
                    time.sleep(0.01)
            elif what == "delete":
                for b in bids[1::2]:
                    gone.add(b)
                    chunk.delete(b)
                    time.sleep(0.005)
            else:
                time.sleep(0.1)
                node.drop_vuid(VUID)
            time.sleep(0.05)
        finally:
            stop.set()

    if what == "compact":
        gone.update(bids[::4])
    assert run_threads([lambda i=i: read(i) for i in range(8)] + [operate]) == []
    assert reads[0] > 0
    if what == "compact":
        assert chunk.gen == 12 and chunk.holes == 0
        for b in set(bids) - gone:
            assert chunk.get(b) == payload[b]
    elif what == "delete":
        for b in bids:
            if b in gone:
                with pytest.raises(NoSuchShard):
                    chunk.get(b)
            else:
                assert chunk.get(b) == payload[b]
    else:
        assert not os.path.exists(chunk._data_path) and chunk._fd == -1
