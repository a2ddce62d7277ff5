"""crc32block's two engines (ISSUE 26, ISSUE 28): the native shard calls of
libcfskv (frame + pwritev, preadv + verify, positional on a descriptor) against
the Python loop, byte for byte; what chooses between them; the counter that
says which one ran; chunk files that read the same under either."""

import ctypes
import os
import sys
import tempfile
import threading

import numpy as np
import pytest

from chubaofs_tpu.blobstore.blobnode import BlobNode
from chubaofs_tpu.blobstore.clustermgr import make_vuid
from chubaofs_tpu.utils import crc32block, exporter, kvstore
from chubaofs_tpu.utils.crc32block import BLOCK_SIZE, CrcError

SIZES = [0, 1, 5120, 5121, 65535, 65536, 65537, 349526, 699051, 1048576]
STRIDE = BLOCK_SIZE + 4
AZ1_SHARD, AZ3_SHARD = 349526, 699051  # 4 MiB / 12 and / 6: six and eleven blocks


@pytest.fixture(scope="module")
def lib():
    lib = kvstore._load_native()
    if lib is None:
        pytest.skip("libcfskv cannot be built here: there is no native engine to test")
    return lib


def force_python(monkeypatch):
    """libcfskv as on a host with no toolchain: the build failed, once."""
    monkeypatch.setattr(kvstore, "_lib", None)
    monkeypatch.setattr(kvstore, "_lib_failed", True)
    assert crc32block.engine() == "python"


def payload_of(n: int, seed: int = 0) -> bytes:
    return np.random.default_rng(seed).integers(0, 256, n, dtype=np.uint8).tobytes()


AT = 3 * 4096 + 7  # where in the scratch file a record goes: positional, never 0


def written(write, size: int) -> bytes:
    """The `size` bytes that `write(fd)` left at AT of a scratch file."""
    with tempfile.TemporaryFile() as f:
        assert write(f.fileno()) == size
        assert os.fstat(f.fileno()).st_size == (AT + size if size else 0)
        return os.pread(f.fileno(), size + 1, AT)


def frame(payload: bytes, prefix: bytes = b"") -> bytes:
    """What `crc32block.pwrite` puts in a file, by the engine it chooses."""
    return written(lambda fd: crc32block.pwrite(fd, AT, payload, prefix=prefix),
                   len(prefix) + crc32block.encoded_len(len(payload)))


def unframe(framed: bytes) -> bytes:
    """`crc32block.pread` of a file that holds `framed`."""
    with tempfile.TemporaryFile() as f:
        os.pwrite(f.fileno(), framed, AT)
        return crc32block.pread(f.fileno(), AT, len(framed))


def native_frame(lib, payload: bytes, prefix: bytes = b"") -> bytes:
    """cfs_shard_pwrite itself, also below the size where pwrite() would call it."""
    return written(lambda fd: lib.cfs_shard_pwrite(fd, AT, prefix, len(prefix), payload,
                                                   len(payload), BLOCK_SIZE),
                   len(prefix) + crc32block.encoded_len(len(payload)))


def native_unframe(lib, framed: bytes) -> tuple[int, bytes]:
    """cfs_shard_pread itself -> (first bad framed offset or -1, payload if -1)."""
    out = ctypes.create_string_buffer(max(1, len(framed)))
    bad = ctypes.c_long()
    with tempfile.TemporaryFile() as f:
        os.pwrite(f.fileno(), framed, AT)
        got = lib.cfs_shard_pread(f.fileno(), AT, len(framed), BLOCK_SIZE, out, ctypes.byref(bad))
    assert got == len(framed)
    return bad.value, out.raw[:crc32block.decoded_len(len(framed))] if bad.value < 0 else b""


def counts() -> dict[tuple[str, str], float]:
    reg = exporter.registry("blobnode")
    return {(e, o): reg.counter("frame_shards_total", {"engine": e, "op": o}).value
            for e in ("native", "python") for o in ("frame", "verify")}


def grown(before: dict) -> dict:
    return {k: v - before[k] for k, v in counts().items() if v != before[k]}


@pytest.mark.parametrize("prefix", [b"", bytes(range(32))], ids=["bare", "prefixed"])
@pytest.mark.parametrize("n", SIZES)
def test_native_frames_as_python_does(lib, monkeypatch, n, prefix):
    payload = payload_of(n, seed=n)
    reference = crc32block.encode(payload, prefix=prefix)  # the loop: the format
    assert frame(payload, prefix) == reference  # the engine pwrite() chooses
    assert native_frame(lib, payload, prefix) == reference
    assert len(reference) == len(prefix) + crc32block.encoded_len(n)
    framed = reference[len(prefix):]
    assert crc32block.decode(framed) == payload
    assert unframe(framed) == payload  # the engine pread() chooses
    assert native_unframe(lib, framed) == (-1, payload)
    force_python(monkeypatch)
    assert frame(payload, prefix) == reference and unframe(framed) == payload


def damaged(framed: bytes, what: str, block: int) -> bytes:
    off = block * STRIDE
    length = min(STRIDE, len(framed) - off) - 4
    at = {"body": off + length // 2, "crc": off + length + 1}.get(what)
    if at is None:
        return framed[: off + {"cut_in_crc": length + 2, "cut_to_stub": 3}[what]]
    return framed[:at] + bytes([framed[at] ^ 0x40]) + framed[at + 1:]


@pytest.mark.parametrize("what", ["body", "crc", "cut_in_crc", "cut_to_stub"])
@pytest.mark.parametrize("n,block", [(AZ1_SHARD, 0), (AZ1_SHARD, 3), (AZ1_SHARD, 5),
                                     (AZ3_SHARD, 10), (70000, 1)])
def test_unframe_rejects_what_the_python_decoder_rejects(lib, monkeypatch, n, block, what):
    """A flip in a block's body or in its crc word, and a tail cut short, fail
    under both engines with the same message: the framed offset of the block."""
    if what.startswith("cut") and (block + 1) * BLOCK_SIZE < n:
        block = (n - 1) // BLOCK_SIZE  # only the last block can be the torn one
    bad = damaged(crc32block.encode(payload_of(n, seed=block)), what, block)
    with pytest.raises(CrcError) as native:
        unframe(bad)
    assert native_unframe(lib, bad)[0] == block * STRIDE
    with pytest.raises(CrcError) as loop:
        crc32block.decode(bad)
    force_python(monkeypatch)
    with pytest.raises(CrcError) as python:
        unframe(bad)
    assert str(native.value) == str(python.value) == str(loop.value)
    if what != "cut_to_stub":  # a stub of <= 4 bytes is refused by its length alone
        assert str(python.value).endswith(f"framed offset {block * STRIDE}")


@pytest.mark.parametrize("offset,size", [(0, 1), (0, AZ1_SHARD), (65535, 2), (65536, 65536),
                                         (100000, 200000), (AZ1_SHARD - 7, 7), (131072, 0)])
def test_block_range_sub_reads_decode_identically(lib, monkeypatch, offset, size):
    payload = payload_of(AZ1_SHARD)
    framed = crc32block.encode(payload)
    fstart, fend = crc32block.block_range(offset, size)
    part = framed[fstart:min(fend, len(framed))]
    inner = offset - fstart // STRIDE * BLOCK_SIZE
    native = unframe(part)
    force_python(monkeypatch)
    assert unframe(part) == native == crc32block.decode(part)
    assert native[inner:inner + size] == payload[offset:offset + size]


@pytest.mark.parametrize("n,engine", [(1, "python"), (2048, "python"), (5120, "python"),
                                      (5121, "native"), (65536, "native"),
                                      (AZ1_SHARD, "native"), (AZ3_SHARD, "native")])
def test_one_count_a_shard_under_the_engine_that_ran(lib, tmp_path, n, engine):
    """A multi-block shard is one native call each way; a shard of one block of
    at most 5 KiB stays on the inline loop (zlib keeps the lock for it)."""
    node = BlobNode(node_id=1, disk_roots=[str(tmp_path / "d0")])
    vuid = make_vuid(1, 0)
    node.create_vuid(vuid)
    payload = payload_of(n)
    before = counts()
    node.put_shard(vuid, 7, payload)
    assert grown(before) == {(engine, "frame"): 1}
    before = counts()
    assert node.get_shard(vuid, 7) == payload
    assert grown(before) == {(engine, "verify"): 1}
    node.close()


def test_many_threads_frame_and_verify_at_once(lib):
    """Write and read workers share the library handle, ONE descriptor and the
    counters; with the lock changing hands every 10 us none of them may lose a
    shard or a count, or see another's bytes at its own position."""
    payloads = [payload_of(n, seed=n) for n in (AZ1_SHARD, AZ3_SHARD, 70000, 2048)]
    want = [crc32block.encode(p) for p in payloads]
    wrong, rounds, workers = [], 40, 24
    before = counts()
    scratch = tempfile.TemporaryFile()
    fd = scratch.fileno()

    def work(i):
        at = i * (1 << 20) + i
        for r in range(rounds):
            k = (i + r) % len(payloads)
            if crc32block.pwrite(fd, at, payloads[k]) != len(want[k]) \
                    or os.pread(fd, len(want[k]), at) != want[k] \
                    or crc32block.pread(fd, at, len(want[k])) != payloads[k]:
                wrong.append((i, r))

    keep = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(i,)) for i in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(keep)
    assert not any(t.is_alive() for t in threads) and not wrong
    scratch.close()
    calls = rounds * workers
    assert grown(before) == {("native", "frame"): calls * 3 / 4, ("native", "verify"): calls * 3 / 4,
                             ("python", "frame"): calls / 4, ("python", "verify"): calls / 4}


def test_unloadable_library_means_the_python_engine(monkeypatch, tmp_path):
    force_python(monkeypatch)
    before = counts()
    assert unframe(frame(payload_of(AZ1_SHARD))) == payload_of(AZ1_SHARD)
    assert grown(before) == {("python", "frame"): 1, ("python", "verify"): 1}
    kv = kvstore.open_kv(str(tmp_path / "kv"))
    assert kv.engine == "python"
    kv.close()


SHARDS = {1: 1000, 2: 5120, 3: 5121, 4: 100_000, 5: AZ1_SHARD, 6: AZ3_SHARD, 7: 0}


def write_shards(root: str) -> tuple[int, str]:
    node = BlobNode(node_id=1, disk_roots=[root])
    vuid = make_vuid(1, 0)
    node.create_vuid(vuid)
    for bid, n in SHARDS.items():
        node.put_shard(vuid, bid, payload_of(n, seed=bid))
    path = node._chunk(vuid)._data_path
    node.close()
    return vuid, path


@pytest.mark.parametrize("writer,reader", [("native", "python"), ("python", "native")])
def test_chunk_written_by_one_engine_serves_under_the_other(lib, monkeypatch, tmp_path,
                                                            writer, reader):
    """Read, ranged read, scrub, inspect, delete + compact, read again: all
    under the engine that did not write the file; and the file itself is the
    same bytes whichever engine wrote it."""
    def use(engine):
        monkeypatch.undo()
        if engine == "python":
            force_python(monkeypatch)
        assert crc32block.engine() == engine

    use(writer)
    vuid, path = write_shards(str(tmp_path / "a"))
    use(reader)
    _, other = write_shards(str(tmp_path / "b"))
    with open(path, "rb") as f, open(other, "rb") as g:
        assert f.read() == g.read()

    node = BlobNode(node_id=1, disk_roots=[str(tmp_path / "a")], scrub_rate=0)
    before = counts()
    for bid, n in SHARDS.items():
        assert node.get_shard(vuid, bid) == payload_of(n, seed=bid)
    assert node.get_shard(vuid, 5, offset=70000, size=1234) == payload_of(AZ1_SHARD, seed=5)[70000:71234]
    if reader == "python":
        assert not any(e == "native" for e, _ in grown(before))
    assert node.scrub_once(max_shards=64) == {"scanned": len(SHARDS), "bad": [], "complete": True}
    assert node.inspect_once() == []
    node.delete_shard(vuid, 4)
    node.delete_shard(vuid, 6)
    chunk = node._chunk(vuid)
    assert chunk.compact() >= crc32block.encoded_len(SHARDS[4]) + crc32block.encoded_len(SHARDS[6])
    for bid, n in SHARDS.items():
        if bid not in (4, 6):
            assert node.get_shard(vuid, bid) == payload_of(n, seed=bid)
    # bit rot in the compacted file is still found, block by block
    with open(chunk._data_path, "r+b") as f:
        f.seek(chunk.shards[5].offset + 32 + 2 * STRIDE + 9)
        byte = f.read(1)
        f.seek(-1, os.SEEK_CUR)
        f.write(bytes([byte[0] ^ 1]))
    with pytest.raises(CrcError, match=f"framed offset {2 * STRIDE}"):
        node.get_shard(vuid, 5)
    assert node.inspect_once() == [(vuid, 5)]
    node.close()
