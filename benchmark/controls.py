"""Controls for the output check: each breaks one guarantee the configuration
states, underneath the timed path, so that `correct` must come out false. Never
part of a measured run (run.py --control stamps every line)."""

from __future__ import annotations

import numpy as np


def parity_flip(dep, seed: int) -> None:
    """One wrong byte in the first output row of every codec job: a wrong
    parity byte on every PUT stripe, a wrong data byte in every decoded blob."""
    from chubaofs_tpu.ops import rs

    sound = rs.gf_matmul_hostbatch
    col = seed % 1021

    def broken(mat_bits, shards):
        out = np.array(sound(mat_bits, shards))
        out[..., 0, col % out.shape[-1]] ^= 1
        return out

    rs.gf_matmul_hostbatch = broken


def short_quorum(dep, seed: int) -> None:
    """Blobnodes silently drop the shard writes of the stripe positions from
    put_quorum - 1 up: every stripe is acknowledged one shard short of quorum."""
    from chubaofs_tpu.blobstore.blobnode import BlobNode

    sound = BlobNode.put_shard
    modes = {m: v["put_quorum"] for m, v in dep.config["modes"].items()}
    codes = {}

    def quorum_of(vuid: int) -> int:
        vid = vuid >> 24
        if vid not in codes:
            from chubaofs_tpu.codec.codemode import CodeMode

            codes[vid] = modes[CodeMode(dep.cluster.cm.get_volume(vid).code_mode).name]
        return codes[vid]

    def broken(self, vuid, bid, payload):
        if (vuid >> 8) & 0xFFFF >= quorum_of(vuid) - 1:
            return None
        return sound(self, vuid, bid, payload)

    BlobNode.put_shard = broken


CONTROLS = {"parity_flip": parity_flip, "short_quorum": short_quorum}


def apply(name: str, dep, seed: int) -> None:
    CONTROLS[name](dep, seed)
