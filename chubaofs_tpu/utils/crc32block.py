"""Block-framed CRC32 codec for chunk datafiles.

Equivalent of reference blobstore/common/crc32block: payloads are framed as
fixed-size blocks, each followed by a 4-byte CRC32 of that block, so torn writes
and bit rot are detected at read time block-by-block (a full-payload CRC can't
say *where* corruption happened and forces whole-shard reads).

Frame layout for payload P split into blocks of BLOCK_SIZE:
    [block0][crc32(block0)][block1][crc32(block1)]...[blockN (short)][crc32]

Two engines, one format (a chunk written by either reads under the other):

- native: `cfs_frame` / `cfs_unframe` of libcfskv (native/kvstore/kvstore.cc),
  the library the blobnode already loads for its metadb. One call walks every
  block, so a shard costs ONE release of the interpreter lock whatever its
  size. That count, not the bytes, is what a shard costs in a daemon of forty
  threads: the Python loop below lets the lock go once a block (`zlib.crc32`
  drops it for every buffer over 5 KiB) and wins it back in about a
  millisecond each time, 7-14 ms a shard for a framing that takes 0.2 ms.
- python: the loop, for a payload of one block of at most 5 KiB (`zlib.crc32`
  keeps the lock for those, so a native call would ADD a release: small-object
  shards), where the library cannot be built (the rule `kvstore.PyKV`
  follows), and as the reference the tests hold the native engine to.

`encode` / `decode` choose from their input; there is no option. Each call
adds one to cfs_blobnode_frame_shards_total{engine, op}.
"""

from __future__ import annotations

import ctypes
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
    """The engine that frames every shard the 5 KiB rule does not keep inline."""
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
    what a chunk file holds for a shard, written with one `write`."""
    n = len(payload)
    size = len(prefix) + encoded_len(n, block_size)
    lib = _native(n)
    _shards["native" if lib else "python", "frame"].add()
    if lib is not None:
        out = _new_bytes(None, size)  # unshared until returned: the library fills it
        lib.cfs_frame(bytes(payload), n, block_size, prefix, len(prefix), out)
        return out
    view = memoryview(payload)
    out = bytearray(size)
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
    n = len(framed)
    size = decoded_len(n, block_size)
    stride = block_size + 4
    lib = _native(size)
    _shards["native" if lib else "python", "verify"].add()
    if lib is not None:
        out = _new_bytes(None, size)
        bad = lib.cfs_unframe(bytes(framed), n, block_size, out)
        if bad >= 0:
            raise CrcError(f"crc mismatch in block at framed offset {bad}")
        return out
    view = memoryview(framed)
    out = bytearray(size)
    pos = 0
    for off in range(0, n, stride):
        frame = view[off : off + stride]
        block, crc_raw = frame[:-4], frame[-4:]
        (want,) = _CRC.unpack(crc_raw)
        if zlib.crc32(block) != want:
            raise CrcError(f"crc mismatch in block at framed offset {off}")
        out[pos : pos + len(block)] = block
        pos += len(block)
    return bytes(out)


def block_range(offset: int, size: int, block_size: int = BLOCK_SIZE) -> tuple[int, int]:
    """Map a payload byte range to the framed byte range covering it.

    Returns (framed_start, framed_end) such that decoding that slice yields the
    blocks containing [offset, offset+size)."""
    first = offset // block_size
    last = -(-(offset + size) // block_size) if size else first
    stride = block_size + 4
    return first * stride, last * stride
