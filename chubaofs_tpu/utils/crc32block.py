"""Block-framed CRC32 codec for chunk datafiles, and the positional I/O of a
framed shard.

Equivalent of reference blobstore/common/crc32block: payloads are framed as
fixed-size blocks, each followed by a 4-byte CRC32 of that block, so torn writes
and bit rot are detected at read time block-by-block (a full-payload CRC can't
say *where* corruption happened and forces whole-shard reads).

Frame layout for payload P split into blocks of BLOCK_SIZE:
    [block0][crc32(block0)][block1][crc32(block1)]...[blockN (short)][crc32]

`encode` / `decode` are the format in memory: a plain loop, the definition the
tests hold everything else to. A chunk file is written and read through
`pwrite` / `pread`, positional on the chunk's descriptor (no file position, no
user-space buffer: nothing to seek, nothing to flush), by one of two engines
with one format (a chunk written by either reads under the other):

- native: `cfs_shard_pwrite` / `cfs_shard_pread` of libcfskv
  (native/kvstore/kvstore.cc), the library the blobnode already loads for its
  metadb. One call checksums (or verifies) every block AND moves the bytes:
  header + (block, crc) pairs as ONE `pwritev` straight from the caller's
  payload, or ONE `preadv` that scatters the blocks to their place in the
  result and the crcs aside. A shard costs ONE release of the interpreter lock
  whatever its size, and no framed copy of it is ever built. That count, not
  the bytes, is what a shard costs in a daemon of forty threads: every call
  that lets the lock go wins it back in about a millisecond; `seek`, `write`,
  `flush` and a framing loop that drops it once a block (`zlib.crc32` does for
  every buffer over 5 KiB) were 4-14 ms a shard for 0.2 ms of work.
- python: `encode` + `os.pwrite`, `os.pread` + `decode`, for a payload of one
  block of at most 5 KiB (`zlib.crc32` keeps the lock for those and the
  syscall is the one release either way: small-object shards) and where the
  library cannot be built (the rule `kvstore.PyKV` follows).

`pwrite` / `pread` choose from their input; there is no option. Each call adds
one to cfs_blobnode_frame_shards_total{engine, op}.

What they guarantee is what `write` + `flush` of a buffered file did: when
`pwrite` returns the whole record is in the OS (page cache), not synced to the
medium; a transfer the OS cuts short is resumed or raised as OSError, never
returned short; `pread` verifies every block it returns.
"""

from __future__ import annotations

import ctypes
import errno
import os
import struct
import zlib

from chubaofs_tpu.utils import exporter, kvstore

BLOCK_SIZE = 64 * 1024
_CRC = struct.Struct("<I")
# zlib.crc32 releases the interpreter lock above this many bytes
_ZLIB_HOLDS_LOCK = 5 * 1024

_new_bytes = ctypes.pythonapi.PyBytes_FromStringAndSize
_new_bytes.restype = ctypes.py_object
_new_bytes.argtypes = [ctypes.c_char_p, ctypes.c_ssize_t]

_shards = {(engine, op): exporter.registry("blobnode").counter(
               "frame_shards_total", {"engine": engine, "op": op})
           for engine in ("native", "python") for op in ("frame", "verify")}


def engine() -> str:
    """The engine that writes and reads every shard the 5 KiB rule does not keep inline."""
    return "native" if kvstore._load_native() is not None else "python"


def _native(payload_len: int):
    """The library, or None where the Python loop is the better engine (one
    block that `zlib.crc32` checks without letting the lock go) or the only one."""
    return kvstore._load_native() if payload_len > _ZLIB_HOLDS_LOCK else None


class CrcError(ValueError):
    """A framed block failed its CRC check."""


def encoded_len(payload_len: int, block_size: int = BLOCK_SIZE) -> int:
    if payload_len == 0:
        return 0
    nblocks = -(-payload_len // block_size)
    return payload_len + 4 * nblocks


def decoded_len(framed_len: int, block_size: int = BLOCK_SIZE) -> int:
    if framed_len == 0:
        return 0
    full = framed_len // (block_size + 4)
    rem = framed_len - full * (block_size + 4)
    if rem == 0:
        return full * block_size
    if rem <= 4:
        raise CrcError(f"framed length {framed_len} leaves a truncated block")
    return full * block_size + (rem - 4)


def encode(payload: bytes | bytearray | memoryview, block_size: int = BLOCK_SIZE,
           prefix: bytes = b"") -> bytes:
    """`prefix` (a record header) followed by the framed payload, one buffer:
    what a chunk file holds for a shard."""
    view = memoryview(payload)
    n = len(view)
    out = bytearray(len(prefix) + encoded_len(n, block_size))
    out[: len(prefix)] = prefix
    pos = len(prefix)
    for off in range(0, n, block_size):
        block = view[off : off + block_size]
        out[pos : pos + len(block)] = block
        pos += len(block)
        _CRC.pack_into(out, pos, zlib.crc32(block))
        pos += 4
    return bytes(out)


def decode(framed: bytes | bytearray | memoryview, block_size: int = BLOCK_SIZE) -> bytes:
    """The payload of `framed`, every block verified; CrcError names the
    framed offset of the first block that fails."""
    view = memoryview(framed)
    out = bytearray(decoded_len(len(view), block_size))
    stride = block_size + 4
    pos = 0
    for off in range(0, len(view), stride):
        frame = view[off : off + stride]
        block, crc_raw = frame[:-4], frame[-4:]
        (want,) = _CRC.unpack(crc_raw)
        if zlib.crc32(block) != want:
            raise CrcError(f"crc mismatch in block at framed offset {off}")
        out[pos : pos + len(block)] = block
        pos += len(block)
    return bytes(out)


def pwrite_all(fd: int, data: bytes | bytearray | memoryview, pos: int) -> None:
    """`os.pwrite` of all of `data` at `pos`, resumed where the OS cut it short."""
    view = memoryview(data)
    done = 0
    while done < len(view):
        n = os.pwrite(fd, view[done:], pos + done)
        if n <= 0:
            raise OSError(errno.EIO, f"pwrite moved nothing at {pos + done}")
        done += n


def pread_exact(fd: int, n: int, pos: int) -> bytes:
    """The `n` bytes at `pos`; a file that ends before them is an OSError."""
    data = os.pread(fd, n, pos)
    while len(data) < n:
        more = os.pread(fd, n - len(data), pos + len(data))
        if not more:
            raise OSError(errno.EIO, f"short read: {len(data)} of {n} bytes at {pos}")
        data += more
    return data


def pwrite(fd: int, pos: int, payload: bytes | bytearray | memoryview,
           prefix: bytes = b"", block_size: int = BLOCK_SIZE) -> int:
    """Write `prefix` + the framed payload at `pos` of `fd`; returns the bytes
    written, always `len(prefix) + encoded_len(len(payload))`. OSError if the
    OS refuses."""
    n = len(payload)
    lib = _native(n)
    _shards["native" if lib else "python", "frame"].add()
    if lib is None:
        record = encode(payload, block_size, prefix)
        pwrite_all(fd, record, pos)
        return len(record)
    wrote = lib.cfs_shard_pwrite(fd, pos, prefix, len(prefix), bytes(payload), n, block_size)
    if wrote < 0:
        raise OSError(-wrote, os.strerror(-wrote))
    return wrote


def pread(fd: int, pos: int, framed_len: int, block_size: int = BLOCK_SIZE) -> bytes:
    """The payload of the `framed_len` framed bytes at `pos` of `fd` (whole
    blocks: what `block_range` gives), every block verified. CrcError names
    the framed offset of the first block that fails; a file that ends before
    `pos + framed_len` is an OSError, never a short payload."""
    size = decoded_len(framed_len, block_size)
    lib = _native(size)
    _shards["native" if lib else "python", "verify"].add()
    if lib is None:
        return decode(pread_exact(fd, framed_len, pos), block_size)
    out = _new_bytes(None, size)  # unshared until returned: the library fills it
    bad = ctypes.c_long()
    got = lib.cfs_shard_pread(fd, pos, framed_len, block_size, out, ctypes.byref(bad))
    if got < 0:
        raise OSError(-got, os.strerror(-got))
    if got < framed_len:
        raise OSError(errno.EIO, f"short read: {got} of {framed_len} bytes at {pos}")
    if bad.value >= 0:
        raise CrcError(f"crc mismatch in block at framed offset {bad.value}")
    return out


def block_range(offset: int, size: int, block_size: int = BLOCK_SIZE) -> tuple[int, int]:
    """Map a payload byte range to the framed byte range covering it.

    Returns (framed_start, framed_end) such that decoding that slice yields the
    blocks containing [offset, offset+size)."""
    first = offset // block_size
    last = -(-(offset + size) // block_size) if size else first
    stride = block_size + 4
    return first * stride, last * stride
