"""The blobstore with libcfskv made unloadable (ISSUE 26): every case of
tests/test_blobstore.py and tests/test_hygiene.py again, collected here a
second time, on the Python framing engine and the Python KV engine — what a
host with no C++ toolchain runs (`kvstore.PyKV`'s rule, now crc32block's too)."""

import pytest

from chubaofs_tpu.utils import crc32block, exporter

from test_blobstore import *  # noqa: F401,F403 (the cases and their fixtures)
from test_crc32block import force_python
from test_hygiene import *  # noqa: F401,F403


@pytest.fixture(autouse=True)
def library_unloadable(monkeypatch):
    force_python(monkeypatch)
    native = exporter.registry("blobnode").counter(
        "frame_shards_total", {"engine": "native", "op": "frame"})
    before = native.value
    yield
    assert crc32block.engine() == "python" and native.value == before
